"""Transactional write path: commit protocol, exactly-once under kill,
Delta commit retry/conflicts, vacuum (io/committer.py, delta/table.py
OptimisticTransaction, tools vacuum).

The full seeded corpus is ``python scale_test.py --chaos`` (run_write_chaos);
this tier-1 slice pins every contract on small frames:
* staged writes + atomic promotion + the _SUCCESS manifest;
* a killed write leaves old data untouched and sweeps staging;
* reruns and runtime-fallback replays converge exactly-once;
* a requeued service write is idempotent by job uuid;
* Delta blind appends rebase through the retry loop, true conflicts
  raise typed, failed transactions sweep their orphans;
* vacuum (library + CLI, dry-run default) reports/removes orphans.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from spark_rapids_tpu.io.committer import (
    TEMP_DIR,
    WRITE_METRICS,
    WriteJob,
    read_manifest,
    sweep_active_jobs,
)
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER, FAULTS
from spark_rapids_tpu.session import TpuSession


@pytest.fixture(autouse=True)
def _clean_fault_state():
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()
    yield
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()


def _df(s, n=40):
    return s.create_dataframe({
        "k": [f"k{i % 3}" for i in range(n)],
        "v": list(range(n))})


def _visible_parts(path):
    """Files a scan would list (hidden files/dirs pruned)."""
    out = []
    for root, dirs, files in os.walk(path):
        dirs[:] = [d for d in dirs if not d.startswith(("_", "."))]
        out.extend(f for f in files if not f.startswith(("_", ".")))
    return sorted(out)


# -- commit protocol ---------------------------------------------------------

def test_write_commits_manifest(session, tmp_path):
    out = str(tmp_path / "t")
    stats = _df(session).write_parquet(out).to_pydict()
    m = read_manifest(out)
    assert m is not None and m["numFiles"] == stats["numFiles"][0]
    assert m["numRows"] == stats["numRows"][0] == 40
    assert m["numBytes"] == stats["numBytes"][0] > 0
    assert sorted(m["files"]) == _visible_parts(out)
    assert m["jobId"]
    assert not os.path.exists(os.path.join(out, TEMP_DIR))


def test_standalone_writer_commits(tmp_path):
    """Direct write_csv (no session) runs the whole protocol itself."""
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.io.csv import write_csv
    out = str(tmp_path / "c")
    files = write_csv(HostTable.from_pydict({"a": [1, 2, 3]}), out)
    assert files == [os.path.join(out, "part-00000.csv")]
    assert os.path.exists(files[0])
    assert read_manifest(out)["files"] == ["part-00000.csv"]
    assert not os.path.exists(os.path.join(out, TEMP_DIR))


# -- exactly-once under kills ------------------------------------------------

@pytest.mark.chaos
def test_kill_mid_file_write_aborts_clean(tmp_path):
    s = TpuSession({
        "spark.rapids.test.faults": "io.write.file:crash:1",
        "spark.rapids.sql.runtimeFallback.enabled": "false"})
    out = str(tmp_path / "k")
    df = _df(s)
    node = P.WriteFiles(df.plan, "parquet", out, ["k"], {})
    with pytest.raises(Exception):
        s.execute(node)
    # nothing reader-visible, no marker, staging swept
    assert _visible_parts(out) == []
    assert read_manifest(out) is None
    assert not os.path.exists(os.path.join(out, TEMP_DIR))
    # rerun: the armed count is spent; the SAME plan converges
    s.execute(node)
    clean = str(tmp_path / "clean")
    _df(s).write_parquet(clean, partition_by=["k"])
    assert sorted(s.read_parquet(out).collect(), key=repr) == \
        sorted(s.read_parquet(clean).collect(), key=repr)


@pytest.mark.chaos
def test_kill_mid_task_commit_rolls_back_promoted(tmp_path):
    """A crash DURING promotion (some files already renamed into place)
    must roll the promoted subset back — readers never see a partial
    job."""
    s = TpuSession({
        "spark.rapids.test.faults": "io.write.commit:crash:2",
        "spark.rapids.sql.runtimeFallback.enabled": "false"})
    out = str(tmp_path / "p")
    df = _df(s)  # 3 partitions -> 3 files, crash on the SECOND rename
    node = P.WriteFiles(df.plan, "parquet", out, ["k"], {})
    with pytest.raises(Exception):
        s.execute(node)
    assert _visible_parts(out) == []
    assert not os.path.exists(os.path.join(out, TEMP_DIR))


@pytest.mark.chaos
def test_crash_mid_write_replays_exactly_once(tmp_path):
    """With the runtime-fallback replay armed (the default), a crash
    mid-write replays transparently and the committed output is
    exactly-once — no doubled or torn files."""
    s = TpuSession({"spark.rapids.test.faults": "io.write.file:crash:1"})
    out = str(tmp_path / "r")
    stats = _df(s).write_parquet(out, partition_by=["k"]).to_pydict()
    assert (s.last_fault_replays or 0) == 1
    m = read_manifest(out)
    assert m["numFiles"] == stats["numFiles"][0] == 3
    assert _visible_parts(out) == sorted(
        os.path.basename(f) for f in m["files"])
    assert s.read_parquet(out).count() == 40


@pytest.mark.chaos
def test_killed_overwrite_keeps_old_data_visible(tmp_path):
    out = str(tmp_path / "o")
    clean = TpuSession()
    _df(clean, 10).write_parquet(out)
    before = sorted(clean.read_parquet(out).collect())
    s = TpuSession({
        "spark.rapids.test.faults": "io.write.file:crash:1",
        "spark.rapids.sql.runtimeFallback.enabled": "false"})
    with pytest.raises(Exception):
        s.execute(P.WriteFiles(_df(s).plan, "parquet", out, None, {}))
    # the reader's view is EXACTLY the old data
    assert sorted(clean.read_parquet(out).collect()) == before


def test_abort_mid_promotion_restores_clobbered_originals(tmp_path):
    """An overwrite whose promotion clobbers an earlier job's files at
    the SAME relative paths, then dies partway: abort must RESTORE the
    originals from backup — unlinking them would destroy the only copy
    of committed data the old manifest still references."""
    out = str(tmp_path / "c")
    os.makedirs(out)
    for rel in ("part-00000.parquet", "part-00001.parquet"):
        with open(os.path.join(out, rel), "w") as f:
            f.write(f"OLD:{rel}")
    job = WriteJob(out)
    for rel in ("part-00000.parquet", "part-00001.parquet"):
        with open(job.stage_path(rel), "w") as f:
            f.write(f"NEW:{rel}")
    # first file promoted OVER the original, then the job dies before
    # the rest (partial promotion is exactly the dangerous window)
    job._staged, rest = job._staged[:1], job._staged[1:]
    job.commit_task()
    assert open(os.path.join(out, "part-00000.parquet")).read() == \
        "NEW:part-00000.parquet"
    job._staged = rest
    job.abort()
    for rel in ("part-00000.parquet", "part-00001.parquet"):
        assert open(os.path.join(out, rel)).read() == f"OLD:{rel}"
    assert not os.path.exists(os.path.join(out, TEMP_DIR))


def test_requeued_write_idempotent_by_job_uuid(tmp_path):
    """Re-executing the SAME WriteFiles node (what the query service's
    worker-loss replay does) after a committed job serves the manifest
    stats and writes nothing."""
    s = TpuSession()
    out = str(tmp_path / "i")
    node = P.WriteFiles(_df(s).plan, "parquet", out, None, {})
    r1 = s.execute(node).to_pydict()
    f = os.path.join(out, "part-00000.parquet")
    mtime = os.path.getmtime(f)
    before = WRITE_METRICS["filesWritten"]
    r2 = s.execute(node).to_pydict()
    assert r1 == r2
    assert WRITE_METRICS["filesWritten"] == before
    assert os.path.getmtime(f) == mtime


@pytest.mark.chaos
def test_partitioned_write_fires_fault_point(tmp_path):
    """The io.write.file point fires on the PARTITIONED branch too —
    it used to fire only on single-file writes, leaving dynamic
    partition writes invisible to the chaos harness."""
    s = TpuSession({
        "spark.rapids.test.faults": "io.write.file:crash:1",
        "spark.rapids.sql.runtimeFallback.enabled": "false"})
    with pytest.raises(Exception):
        _df(s).write_parquet(str(tmp_path / "f"), partition_by=["k"])
    assert FAULTS.counters().get("io.write.file") == 1


def test_crash_handler_sweep_clears_staging(tmp_path):
    out = str(tmp_path / "s")
    job = WriteJob(out)
    staged = job.stage_path("part-00000.parquet")
    with open(staged, "w") as f:
        f.write("torn")
    assert sweep_active_jobs() >= 1
    assert not os.path.exists(os.path.join(out, TEMP_DIR))
    assert sweep_active_jobs() == 0  # job unregistered


# -- listing hygiene (io/common.py satellite) --------------------------------

def test_expand_paths_prunes_hidden_dirs_and_files(tmp_path):
    from spark_rapids_tpu.io.common import expand_paths
    d = tmp_path / "data"
    (d / TEMP_DIR / "job1" / "0").mkdir(parents=True)
    (d / ".stage").mkdir()
    (d / "sub").mkdir()
    (d / "a.parquet").write_text("x")
    (d / "sub" / "b.parquet").write_text("x")
    (d / "_SUCCESS").write_text("{}")
    (d / ".hidden").write_text("x")
    # staged part file does NOT start with '_' — only directory
    # pruning keeps it out of the scan
    (d / TEMP_DIR / "job1" / "0" / "part-00000.parquet").write_text("x")
    (d / ".stage" / "part-00001.parquet").write_text("x")
    got = expand_paths([str(d)])
    assert got == [str(d / "a.parquet"), str(d / "sub" / "b.parquet")]
    # glob branch filters _/. basenames too (_SUCCESS, _temporary,
    # .hidden all matched "*" before this fix)
    got_glob = expand_paths([str(d / "*")])
    assert str(d / "a.parquet") in got_glob
    assert not any(os.path.basename(p).startswith(("_", "."))
                   for p in got_glob)
    # a glob CROSSING a hidden dir must not surface staged files —
    # only the wildcard-matched components are checked, so a caller
    # explicitly naming a hidden prefix still gets their files
    from spark_rapids_tpu.errors import ColumnarProcessingError
    with pytest.raises(ColumnarProcessingError, match="no input files"):
        expand_paths([str(d / "*" / "*" / "*" / "*.parquet")])
    explicit = expand_paths([str(d / TEMP_DIR / "job1" / "0" / "*")])
    assert explicit == [str(d / TEMP_DIR / "job1" / "0"
                            / "part-00000.parquet")]


def test_vacuum_spares_inflight_staging_and_retention(tmp_path):
    from spark_rapids_tpu.tools.vacuum import run_vacuum
    out = str(tmp_path / "live")
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.io.csv import write_csv
    write_csv(HostTable.from_pydict({"a": [1]}), out)
    # a job in flight over the same destination
    job = WriteJob(out)
    staged = job.stage_path("part-00001.csv")
    with open(staged, "w") as f:
        f.write("a\n2\n")
    rep = run_vacuum(out, delete=True)
    assert rep["orphans"] == []  # live staging is not an orphan
    assert os.path.exists(staged)
    # promoted-but-not-yet-manifested files are protected too: between
    # commit_task and commit_job the old manifest doesn't list them,
    # but a concurrent vacuum must not unlink them under the live job
    promoted = job.commit_task()
    assert run_vacuum(out, delete=True)["orphans"] == []
    assert all(os.path.exists(p) for p in promoted)
    job.abort()
    # dead staging younger than the retention window is kept too
    dead = os.path.join(out, TEMP_DIR, "deadjob", "0", "x.csv")
    os.makedirs(os.path.dirname(dead))
    with open(dead, "w") as f:
        f.write("torn")
    assert run_vacuum(out, retention_hours=1.0)["orphans"] == []
    rep2 = run_vacuum(out, delete=True)  # retention 0: swept
    assert rep2["deleted"] == 1 and not os.path.exists(dead)


# -- Delta: conflict classification + retry ----------------------------------

def _make_delta(session, path, n=20):
    from spark_rapids_tpu.delta.table import write_delta
    write_delta(_df(session, n).plan, session, path, mode="error")


def test_delta_concurrent_disjoint_appends_both_land(session, tmp_path):
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.delta.log import DeltaLog
    from spark_rapids_tpu.delta.table import (
        OptimisticTransaction,
        _write_data_file,
    )
    path = str(tmp_path / "dt")
    _make_delta(session, path)
    log = DeltaLog(path)
    base = log.latest_version()
    retries0 = WRITE_METRICS["commitRetries"]
    errs = []
    barrier = threading.Barrier(2)

    def append(tag):
        txn = OptimisticTransaction(log, session.conf, read_version=base)
        txn.stage(_write_data_file(path, HostTable.from_pydict(
            {"k": [tag], "v": [99]}), {}))
        barrier.wait()  # both read the SAME snapshot, then race
        try:
            txn.commit("WRITE (append)")
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    ts = [threading.Thread(target=append, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert errs == []
    assert log.latest_version() == base + 2
    assert WRITE_METRICS["commitRetries"] > retries0
    assert session.read_delta(path).count() == 22


def test_delta_overlapping_overwrite_raises_typed_and_sweeps(
        session, tmp_path):
    import time as _time

    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.delta.log import (
        DeltaConcurrentWriteException,
        DeltaLog,
        RemoveFile,
    )
    from spark_rapids_tpu.delta.table import (
        OptimisticTransaction,
        _write_data_file,
    )
    path = str(tmp_path / "ow")
    _make_delta(session, path)
    log = DeltaLog(path)
    base = log.latest_version()
    now = int(_time.time() * 1000)

    def overwrite_txn():
        txn = OptimisticTransaction(log, session.conf, read_version=base)
        for a in log.snapshot(base).files:
            txn.stage(RemoveFile(a.path, now))
        txn.stage(_write_data_file(path, HostTable.from_pydict(
            {"k": ["x"], "v": [1]}), {}))
        return txn

    t1, t2 = overwrite_txn(), overwrite_txn()
    t1.commit("WRITE (overwrite)")
    orphan = [a["add"]["path"] for a in t2.actions if "add" in a][0]
    assert os.path.exists(os.path.join(path, orphan))
    with pytest.raises(DeltaConcurrentWriteException):
        t2.commit("WRITE (overwrite)")
    # the loser's staged data file was swept, not left as an orphan
    assert not os.path.exists(os.path.join(path, orphan))
    # the winner's overwrite is intact
    assert session.read_delta(path).count() == 1


def test_delta_metadata_conflict_raises_typed(session, tmp_path):
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.delta.log import (
        DeltaLog,
        DeltaMetadataChangedException,
    )
    from spark_rapids_tpu.delta.table import (
        OptimisticTransaction,
        _write_data_file,
    )
    path = str(tmp_path / "md")
    _make_delta(session, path)
    log = DeltaLog(path)
    base = log.latest_version()
    # a blind append staged against the old snapshot...
    txn = OptimisticTransaction(log, session.conf, read_version=base)
    txn.stage(_write_data_file(path, HostTable.from_pydict(
        {"k": ["z"], "v": [7]}), {}))
    # ...loses to a METADATA winner: rebase would commit rows under a
    # schema/config the writer never saw — must surface typed
    session.delta_table(path).set_properties({"foo": "bar"})
    with pytest.raises(DeltaMetadataChangedException):
        txn.commit("WRITE (append)")


@pytest.mark.chaos
def test_delta_commit_race_injection_retries(tmp_path):
    from spark_rapids_tpu.delta.log import DeltaLog
    from spark_rapids_tpu.delta.table import write_delta
    s = TpuSession({
        "spark.rapids.test.faults": "delta.commit.race:race:1"})
    path = str(tmp_path / "race")
    retries0 = WRITE_METRICS["commitRetries"]
    write_delta(_df(s, 10).plan, s, path, mode="error")
    assert WRITE_METRICS["commitRetries"] == retries0 + 1
    assert DeltaLog(path).latest_version() == 0
    assert s.read_delta(path).count() == 10


def test_delta_retry_budget_conf_exhausts_typed(session, tmp_path):
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.delta.log import (
        DeltaConcurrentModificationException,
        DeltaLog,
    )
    from spark_rapids_tpu.delta.table import (
        OptimisticTransaction,
        _write_data_file,
    )
    path = str(tmp_path / "budget")
    _make_delta(session, path)
    log = DeltaLog(path)
    conf = RapidsConf({"spark.rapids.test.faults":
                       "delta.commit.race:race:99",
                       "spark.rapids.sql.write.maxCommitRetries": "2",
                       "spark.rapids.sql.write.commitRetryWaitMs": "0"})
    FAULTS.arm(str(conf.get("spark.rapids.test.faults")))
    txn = OptimisticTransaction(log, conf,
                                read_version=log.latest_version())
    add = _write_data_file(path, HostTable.from_pydict({"k": ["q"],
                                                        "v": [1]}), {})
    txn.stage(add)
    with pytest.raises(DeltaConcurrentModificationException,
                       match="gave up"):
        txn.commit("WRITE (append)")
    # exhaustion swept the staged file too
    assert not os.path.exists(os.path.join(path, add.path))


# -- vacuum ------------------------------------------------------------------

def test_vacuum_spares_uncommitted_delta_txn_files(session, tmp_path):
    """A Delta transaction's data files land in the table dir BEFORE
    its log commit — a concurrent vacuum (default retention 0) must
    not sweep them; after commit they are live; an abandoned txn's
    protection expires with the object and vacuum reclaims the file."""
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.delta.log import DeltaLog
    from spark_rapids_tpu.delta.table import (
        OptimisticTransaction,
        _write_data_file,
    )
    path = str(tmp_path / "txn")
    _make_delta(session, path)
    log = DeltaLog(path)
    txn = OptimisticTransaction(log, session.conf,
                                read_version=log.latest_version())
    add = _write_data_file(path, HostTable.from_pydict(
        {"k": ["t"], "v": [1]}), {})
    txn.stage(add)
    staged = os.path.join(path, add.path)
    rep = session.delta_table(path).vacuum()  # deleting vacuum
    assert rep["files_deleted"] == 0 and os.path.exists(staged)
    txn.commit("WRITE (append)")
    assert session.delta_table(path).vacuum()["files_deleted"] == 0
    assert session.read_delta(path).count() == 21
    # abandoned txn: file written, never committed, txn dropped
    txn2 = OptimisticTransaction(log, session.conf,
                                 read_version=log.latest_version())
    add2 = _write_data_file(path, HostTable.from_pydict(
        {"k": ["u"], "v": [2]}), {})
    txn2.stage(add2)
    del txn2
    rep2 = session.delta_table(path).vacuum()
    assert rep2["files_deleted"] == 1
    assert not os.path.exists(os.path.join(path, add2.path))


def test_vacuum_dry_run_default_then_delete(session, tmp_path):
    from spark_rapids_tpu.delta.table import write_delta
    from spark_rapids_tpu.tools.vacuum import run_vacuum
    path = str(tmp_path / "v")
    _make_delta(session, path)
    write_delta(_df(session, 5).plan, session, path, mode="overwrite")
    rep = run_vacuum(path)  # DRY RUN default
    assert rep["dryRun"] and rep["deleted"] == 0
    assert len(rep["orphans"]) >= 1
    for rel in rep["orphans"]:
        assert os.path.exists(os.path.join(path, rel))
    rep2 = run_vacuum(path, delete=True)
    assert rep2["deleted"] == len(rep["orphans"])
    assert run_vacuum(path)["orphans"] == []
    assert session.read_delta(path).count() == 5


def test_vacuum_keeps_live_deletion_vectors(session, tmp_path):
    """A DV-carrying snapshot: vacuum must resolve the descriptor's
    encoded path and KEEP the live DV file (matching the raw base85
    token against filenames would sweep it)."""
    from spark_rapids_tpu.ops.expr import col, lit
    path = str(tmp_path / "dv")
    _make_delta(session, path)
    dt = session.delta_table(path)
    dt.delete(col("v") < lit(3))  # partial file -> deletion vector
    before = sorted(session.read_delta(path).collect())
    assert len(before) == 17
    res = dt.vacuum()
    assert res["files_deleted"] == 0
    assert sorted(session.read_delta(path).collect()) == before


def test_vacuum_manifest_dir_and_staging(session, tmp_path):
    from spark_rapids_tpu.tools.vacuum import run_vacuum
    out = str(tmp_path / "m")
    _df(session).write_parquet(out, partition_by=["k"])
    # superseding job: fewer partitions -> old job's extra files are
    # now unreferenced by the manifest
    _df(session, 6).write_parquet(out)
    # plus staging debris of a job that died without abort — incl. a
    # .backup tree (hidden names inside _temporary are still orphans)
    debris = os.path.join(out, TEMP_DIR, "deadjob", "0",
                          "part-00000.parquet")
    backup = os.path.join(out, TEMP_DIR, "deadjob", "0", ".backup",
                          "part-00000.parquet")
    os.makedirs(os.path.dirname(backup))
    for p in (debris, backup):
        with open(p, "w") as f:
            f.write("torn")
    rep = run_vacuum(out)
    assert rep["mode"] == "manifest" and rep["dryRun"]
    assert any("deadjob" in o for o in rep["orphans"])
    assert any(".backup" in o for o in rep["orphans"])
    assert any(o.startswith("k=") for o in rep["orphans"])
    run_vacuum(out, delete=True)
    rep2 = run_vacuum(out)
    assert rep2["orphans"] == []
    assert not os.path.exists(os.path.join(out, TEMP_DIR))
    assert session.read_parquet(out).count() == 6


def test_vacuum_cli_subprocess_smoke(session, tmp_path):
    """CI contract: `tools vacuum` runs as a subprocess, dry-run by
    default (files intact), --delete removes; --json parses."""
    from spark_rapids_tpu.delta.table import write_delta
    path = str(tmp_path / "cli")
    _make_delta(session, path)
    write_delta(_df(session, 5).plan, session, path, mode="overwrite")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "vacuum",
         path, "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["dryRun"] and rep["mode"] == "delta" and rep["orphans"]
    for rel in rep["orphans"]:
        assert os.path.exists(os.path.join(path, rel))
    out2 = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "vacuum",
         path, "--delete", "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out2.returncode == 0, out2.stderr
    assert json.loads(out2.stdout)["deleted"] == len(rep["orphans"])
    for rel in rep["orphans"]:
        assert not os.path.exists(os.path.join(path, rel))


# -- observability -----------------------------------------------------------

def test_event_log_write_fields(tmp_path):
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path / "ev")})
    _df(s).write_parquet(str(tmp_path / "w"), partition_by=["k"])
    rec = s.last_event_record
    assert rec["schema"] == 16
    assert rec["filesWritten"] == 3
    assert rec["bytesWritten"] > 0
    assert rec["commitRetries"] == 0
    # a read-only query on the same session records zeros
    s.read_parquet(str(tmp_path / "w")).count()
    rec2 = s.last_event_record
    assert rec2["filesWritten"] == 0 and rec2["bytesWritten"] == 0
