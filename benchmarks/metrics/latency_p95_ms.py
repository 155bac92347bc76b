import math


def read(run):
    lat = sorted(s.latency_s for s in run.statements)
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1] * 1e3
