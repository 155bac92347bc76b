"""System connector: engine state as tables (reference: the system
connector's system.runtime/system.metadata + the jmx connector)."""

import pytest


@pytest.fixture()
def runner():
    from presto_tpu.runner import LocalRunner
    return LocalRunner("tpch", "tiny")


def test_catalogs(runner):
    rows = runner.execute(
        "select catalog_name from system.metadata.catalogs "
        "order by catalog_name").rows()
    names = [r[0] for r in rows]
    for expected in ("tpch", "tpcds", "memory", "file", "system"):
        assert expected in names


def test_tables_listing(runner):
    n = runner.execute(
        "select count(*) from system.metadata.tables "
        "where table_catalog = 'tpcds' and table_schema = 'tiny'"
    ).rows()[0][0]
    assert n == 24  # the full TPC-DS schema


def test_query_history(runner):
    held = runner.execute("select count(*) from nation")
    runner.execute("select count(*) from region")  # result discarded
    with pytest.raises(Exception):
        runner.execute("select * from nope")
    rows = runner.execute(
        "select query_id, state, output_rows, query "
        "from system.runtime.queries order by query_id").rows()
    # row counts resolve lazily from weakly-held results: alive -> the
    # count (no sync on the producing query's timed path), gone -> -1
    assert rows[0][1] == "FINISHED" and rows[0][2] == 1
    assert rows[1][1] == "FINISHED" and rows[1][2] == -1
    assert rows[2][1] == "FAILED"
    # the observing query sees itself mid-flight
    assert rows[-1][1] == "RUNNING"
    assert "system.runtime.queries" in rows[-1][3]
    del held


def test_nodes(runner):
    rows = runner.execute(
        "select node_id, http_uri, state, executor_queued, "
        "reserved_bytes from system.runtime.nodes").rows()
    assert rows[0][:3] == ("local-0", "local://in-process", "active")
    # load gauges are live ints (the observing query itself may hold
    # a reservation)
    assert rows[0][3] >= 0 and rows[0][4] >= 0


def test_joins_against_system_tables(runner):
    """System tables are ordinary relations: join them."""
    rows = runner.execute(
        "select t.table_schema, count(*) c "
        "from system.metadata.tables t "
        "where t.table_catalog = 'tpch' "
        "group by t.table_schema order by t.table_schema").rows()
    assert all(c == 8 for _, c in rows)  # 8 tpch tables per schema
