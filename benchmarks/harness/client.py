"""The benchmark's own client-protocol loop: a copy of
presto_tpu.server.coordinator.StatementClient.execute with the fixed
0.1 s sleep between polls replaced by a capped back-off (first poll at
once, then `first_s` doubling to `cap_s`). A latency read through it
resolves to `cap_s` at worst."""

from __future__ import annotations

import json
import time
import urllib.request


class StatementFailed(RuntimeError):
    pass


def _request(url: str, timeout: float, body: bytes | None = None,
             headers: dict | None = None, method: str | None = None) -> bytes:
    req = urllib.request.Request(url, data=body, method=method)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def execute(server: str, sql: str, *, user: str, timeout_s: float,
            first_s: float, cap_s: float):
    """POST the statement and follow nextUri to the last page.
    Returns (query id, columns, rows). Raises StatementFailed on
    a FAILED state or when `timeout_s` passes (the query is then killed
    server-side, as the program's client does)."""
    deadline = time.monotonic() + timeout_s
    resp = json.loads(_request(
        f"{server}/v1/statement", timeout_s, body=sql.encode(),
        headers={"X-Presto-User": user}))
    qid = resp["id"]
    next_uri = resp["nextUri"]
    columns = None
    rows: list = []
    pause = 0.0
    while True:
        if time.monotonic() > deadline:
            try:
                _request(f"{server}/v1/statement/{qid}", 10,
                         method="DELETE")
            except OSError:
                pass
            raise StatementFailed(
                f"{qid}: no answer within {timeout_s:g} s; kill issued")
        state = json.loads(_request(next_uri, timeout_s))
        if columns is None and "columns" in state:
            columns = state["columns"]
        s = state["stats"]["state"]
        if s == "FAILED":
            err = state.get("error") or {}
            raise StatementFailed(
                f"{qid}: {err.get('errorKind')}: {err.get('message')}")
        if s == "FINISHED":
            rows.extend(state.get("data", []))
            next_uri = state.get("nextUri")
            if next_uri is None:
                return qid, columns, rows
            continue
        next_uri = state["nextUri"]
        if pause:
            time.sleep(pause)
        pause = min(cap_s, pause * 2) if pause else first_s
