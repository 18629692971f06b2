"""Regressions for the round-2/round-3 advisor findings (ADVICE.md):
P2P write idempotency + dead-connection eviction, exact integral
RoundCeil/RoundFloor, speculative aggregate shrink, aborted-attempt
speculation-flag cleanup, embed-by-bytes collect sizing."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops.expr import col, lit
from spark_rapids_tpu.session import TpuSession


# -- P2P shuffle (ADVICE r2: shuffle/p2p.py) ---------------------------------

def _p2p_env():
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.shuffle.p2p import P2PShuffleEnv
    return P2PShuffleEnv(RapidsConf({}), executor_id="exec-advice-test")


def _tables(n_parts, rows=8, seed=0):
    from spark_rapids_tpu.columnar import HostColumn, HostTable
    rng = np.random.default_rng(seed)
    out = []
    for p in range(n_parts):
        out.append(HostTable(["a"], [HostColumn(
            T.LONG, rng.integers(0, 100, rows).astype(np.int64))]))
    return out


def test_p2p_write_partitions_idempotent_under_failure():
    """A failure mid-write must leave no partial map output; the replay's
    rows must appear exactly once (ADVICE r2: non-idempotent
    write_partitions)."""
    env = _p2p_env()
    try:
        handle = env.new_shuffle(3)
        parts = _tables(3)
        # inject a failure on the SECOND add_block of the first attempt
        real_add = env.catalog.add_block
        calls = {"n": 0}

        def flaky(bid, data):
            calls["n"] += 1
            if calls["n"] == 2:
                raise OSError("injected mid-write failure")
            return real_add(bid, data)

        env.catalog.add_block = flaky
        with pytest.raises(OSError):
            handle.write_partitions(parts)
        env.catalog.add_block = real_add
        assert handle.num_maps == 0  # attempt left nothing behind
        assert env.catalog.host_bytes == 0
        handle.write_partitions(parts)  # replay
        assert handle.num_maps == 1

        reader = env.reader(handle)
        total = sum(t.num_rows for p in range(3)
                    for t in reader.read_partition(p))
        assert total == sum(t.num_rows for t in parts)
    finally:
        env.close()


def test_p2p_broken_connection_evicted():
    """A TX_ERROR transport fault marks the connection broken and the env
    reconnects on the next fetch (ADVICE r2: dead sockets cached
    forever)."""
    env = _p2p_env()
    try:
        handle = env.new_shuffle(1)
        handle.write_partitions(_tables(1))
        c1 = env.connection_to(env.executor_id)
        c1.broken = True  # simulate a transport fault
        c2 = env.connection_to(env.executor_id)
        assert c2 is not c1
        rows = sum(t.num_rows for t in env.reader(handle).read_partition(0))
        assert rows == 8
    finally:
        env.close()


def test_tcp_connection_marks_broken_on_socket_error():
    import socket
    from spark_rapids_tpu.shuffle.transport import (
        BounceBufferManager,
        _TcpConnection,
    )
    a, b = socket.socketpair()
    conn = _TcpConnection(a, BounceBufferManager(1 << 16, 2))
    b.close()  # peer dies
    tx = conn.request(1, b"payload")
    assert tx.status == "ERROR"
    assert conn.broken


# -- exact integral RoundCeil/RoundFloor (ADVICE r2: ops/math.py) ------------

def test_round_ceil_floor_exact_above_2_53():
    from spark_rapids_tpu.ops.math import RoundCeil, RoundFloor
    big = 2**60 + 7  # not representable in float64
    vals = np.array([big, -big, 12345, -12345, 0, 999], dtype=np.int64)
    tpu = TpuSession()
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    for sess in (tpu, cpu):
        df = sess.create_dataframe({"x": vals})
        got = df.select(
            RoundCeil(col("x"), lit(-2)).alias("c"),
            RoundFloor(col("x"), lit(-2)).alias("f")).collect()
        for (c, f), x in zip(got, vals.tolist()):
            assert c == -((-x) // 100) * 100, (x, c)
            assert f == (x // 100) * 100, (x, f)


# -- speculative aggregate shrink (ADVICE r3: aggregate.py) ------------------

def test_speculative_shrink_output_correct_and_replays_on_miss():
    """High-reduction sorted-path aggregates shrink speculatively; an
    all-distinct-keys aggregate (speculation miss) replays and still
    returns exact results."""
    n = 200_000  # capacity 262144 > EMBED_NROWS_CAP -> speculation applies
    rng = np.random.default_rng(5)
    tpu = TpuSession()
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})

    # high reduction: few distinct int keys (sorted path, shrink fits)
    data = {"k": rng.integers(0, 50, n).astype(np.int64),
            "v": rng.random(n)}
    q = lambda s: sorted(s.create_dataframe(data).group_by("k")
                         .agg(F.count().alias("c")).collect())
    assert q(tpu) == q(cpu)

    # no reduction: every key distinct -> ngroups > spec bucket -> replay
    data2 = {"k": np.arange(n, dtype=np.int64),
             "v": rng.random(n)}
    q2 = lambda s: sorted(s.create_dataframe(data2).group_by("k")
                          .agg(F.count().alias("c")).collect())[:5]
    assert q2(tpu) == q2(cpu)


# -- aborted-attempt speculation flags (ADVICE r3: join.py/retry) ------------

def test_oom_retry_drops_aborted_attempt_flags():
    """An injected OOM inside a speculative join must not leave the
    aborted attempt's flag pending (a stale True flag would spuriously
    blocklist the site)."""
    rng = np.random.default_rng(9)
    n = 5000
    data = {"k": rng.integers(0, 100, n).astype(np.int64),
            "v": rng.random(n)}
    dim = {"k": np.arange(100, dtype=np.int64),
           "w": np.arange(100, dtype=np.int64) * 2}
    tpu = TpuSession({"spark.rapids.sql.test.injectRetryOOM": "retry:1"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    q = lambda s: sorted(
        s.create_dataframe(data).join(s.create_dataframe(dim), on="k",
                                      how="inner")
        .group_by("w").agg(F.count().alias("c")).collect())
    assert q(tpu) == q(cpu)


# -- embed-by-bytes collect sizing (ADVICE r3: table.py) ---------------------

def test_wide_table_collect_skips_padded_embed():
    """A wide schema whose padded bucket exceeds EMBED_MAX_BYTES takes the
    row-count sync instead of a multi-MB padded fetch — results equal
    either way."""
    from spark_rapids_tpu.columnar.table import DeviceTable
    n = 40_000  # bucket 65536 == EMBED_NROWS_CAP
    rng = np.random.default_rng(11)
    data = {f"c{i}": rng.random(n) for i in range(16)}  # 16 f64 cols
    bytes_per_row = (4 * 2 + 1) * 16
    assert 65536 * bytes_per_row > DeviceTable.EMBED_MAX_BYTES
    tpu = TpuSession()
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    q = lambda s: s.create_dataframe(data).filter(
        col("c0") > lit(0.99)).collect()
    got, want = q(tpu), q(cpu)
    assert len(got) == len(want)
