"""How benchmarks/tests/data/small.xplane.pb was recorded (on the
chip; kept so that it can be recorded again after a JAX upgrade):

    python3 benchmarks/tests/record_trace.py <output directory>

Three marked "statements" on one device: a matrix product run twice,
an elementwise pass, and a sleep with nothing on the device. Prints
the planes, lines and first events, which is what trace_reduce.py and
test_trace_reduce.py were written against."""

import os
import shutil
import sys
import tempfile
import time


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.trace_reduce import find_xplane

    matmul = jax.jit(lambda x: (x @ x).sum())
    scale = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    matmul(x).block_until_ready()
    scale(x).block_until_ready()
    log = tempfile.mkdtemp(prefix="bench-record-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:qa#0"):
        matmul(x).block_until_ready()
        matmul(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench:qb#1"):
        scale(x).block_until_ready()
        time.sleep(0.02)
    with jax.profiler.TraceAnnotation("bench:qc#2"):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(find_xplane(log), dst)
    shutil.rmtree(log, ignore_errors=True)
    print(dst, os.path.getsize(dst), "bytes")
    data = jax.profiler.ProfileData.from_file(dst)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events), [
                (e.name, e.start_ns, e.duration_ns) for e in events[:4]])
            if events and plane.name.startswith("/device:"):
                print("    stats of first:", list(events[0].stats)[:12])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
