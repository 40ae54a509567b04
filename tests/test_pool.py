"""Multi-process serving: frame protocol, journal, cross-process locks,
and the WorkerPool supervisor.

The quick tier drives the pool against the jax-free stub worker in
``tests/_pool_stub.py`` (the supervisor never interprets payloads, so
an echo worker exercises dispatch/replay/probe/crash/drain without a
~10s jax import per subprocess); the ``slow`` tests spawn real
``serve --jsonl`` router workers for the SIGTERM-drain regression and
true cross-process compile coalescing.
"""
import io
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.checkpoint.store import (LockTimeout, _blob_path, blob_lock,
                                    list_blobs)
from repro.launch.errors import (QueueFull, ServiceError, WorkerLost,
                                 error_for_code)
from repro.launch.faults import FaultInjector, active_injector, \
    install_from_env
from repro.launch.pool import RequestJournal, payload_digest, read_frame, \
    write_frame
from repro.launch.supervisor import WorkerPool

HERE = os.path.dirname(os.path.abspath(__file__))
STUB = os.path.join(HERE, "_pool_stub.py")


def stub_pool(n_workers=2, *, stub_env=None, **kw):
    env = dict(os.environ)
    env.update(stub_env or {})
    kw.setdefault("probe_interval_s", 0.1)
    return WorkerPool(n_workers, cmd=[sys.executable, STUB], env=env, **kw)


def wait_for(cond, timeout_s=15.0, msg="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# frame protocol
# ---------------------------------------------------------------------------
def test_frame_roundtrip():
    buf = io.StringIO()
    msgs = [{"op": "submit", "id": "r1", "data": [[1, 2], [3, 4]]},
            {"ok": True, "nested": {"a": [1.5, None, "x"]}}]
    for m in msgs:
        write_frame(buf, m)
    buf.seek(0)
    assert read_frame(buf) == msgs[0]
    assert read_frame(buf) == msgs[1]
    assert read_frame(buf) is None          # EOF


def test_frame_reader_skips_noise_and_resyncs():
    buf = io.StringIO()
    buf.write("some stray log line\n\n")
    write_frame(buf, {"id": 1})
    buf.write("[warning] another stray\n")
    write_frame(buf, {"id": 2})
    buf.seek(0)
    assert read_frame(buf) == {"id": 1}
    assert read_frame(buf) == {"id": 2}


def test_frame_torn_write_reads_as_eof():
    buf = io.StringIO()
    write_frame(buf, {"id": 1, "data": [0] * 50})
    whole = buf.getvalue()
    torn = io.StringIO(whole[:len(whole) - 20])   # killed mid-payload
    assert read_frame(io.StringIO(whole)) == {"id": 1, "data": [0] * 50}
    assert read_frame(torn) is None


# ---------------------------------------------------------------------------
# journal + typed-error wire codes
# ---------------------------------------------------------------------------
def test_journal_counts_and_wal(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    j = RequestJournal(path)
    j.record("dispatch", "r1", worker=0, digest="abc")
    j.record("replay", "r1", worker=1, digest="abc")
    j.record("deliver", "r1", replayed=True)
    j.record("lost", "r2", digest="def")
    assert j.stats() == {"dispatch": 1, "deliver": 1, "typed": 0,
                         "fail": 0, "replay": 1, "lost": 1}
    with pytest.raises(ValueError):
        j.record("nonsense", "r3")
    j.close()
    events = [json.loads(line) for line in open(path)]
    assert [e["ev"] for e in events] == ["dispatch", "replay", "deliver",
                                         "lost"]
    # the WAL is what makes "replayed bit-exact" auditable: the digest
    # at dispatch equals the digest at replay
    assert events[0]["digest"] == events[1]["digest"]


def test_payload_digest_is_content_addressed():
    a = np.arange(12, dtype=np.int32).reshape(3, 4)
    assert payload_digest(a) == payload_digest(a.copy())
    assert payload_digest(a) != payload_digest(a.T.copy())
    assert payload_digest(a) != payload_digest(a.astype(np.int64))


def test_error_for_code_rehydrates_typed_errors():
    e = error_for_code("queue_full", "busy", 1.25)
    assert isinstance(e, QueueFull) and e.retry_after_s == 1.25
    assert isinstance(error_for_code("worker_lost", "gone"), WorkerLost)
    unknown = error_for_code("no_such_code", "x")
    assert isinstance(unknown, ServiceError)
    assert not isinstance(unknown, QueueFull)


# ---------------------------------------------------------------------------
# fault-injector env activation
# ---------------------------------------------------------------------------
def test_fault_injector_from_spec():
    inj = FaultInjector.from_spec(
        "sites=dispatch|fallback;error_count=2;seed=7;match=13x13;"
        "delay_s=0.001;delay_rate=0.5;error_rate=0.25")
    assert inj.sites == ("dispatch", "fallback")
    assert inj.error_count == 2 and inj.seed == 7
    assert inj.match == "13x13" and inj.error_rate == 0.25
    assert inj.delay_s == 0.001 and inj.delay_rate == 0.5
    assert inj.spec and "error_count=2" in inj.spec
    assert inj.stats()["spec"] == inj.spec
    with pytest.raises(ValueError):
        FaultInjector.from_spec("unknown_knob=1")
    with pytest.raises(ValueError):
        FaultInjector.from_spec("error_count")


def test_install_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    assert install_from_env() is None
    monkeypatch.setenv("REPRO_FAULTS", "sites=dispatch;error_count=1")
    inj = install_from_env()
    try:
        assert inj is not None and active_injector() is inj
        with pytest.raises(Exception):
            inj.perturb("dispatch", "any")       # the armed budget fires
    finally:
        inj.__exit__(None, None, None)
    assert active_injector() is not inj


# ---------------------------------------------------------------------------
# cross-process blob locks
# ---------------------------------------------------------------------------
def test_blob_lock_acquire_release(tmp_path):
    d = str(tmp_path)
    with blob_lock(d, "tok") as lk:
        lock_file = _blob_path(d, "tok") + ".lock"
        assert os.path.exists(lock_file)
        info = json.load(open(lock_file))
        assert info["pid"] == os.getpid()
        assert lk["steals"] == 0
    assert not os.path.exists(lock_file)


def test_blob_lock_contention_waits(tmp_path):
    d = str(tmp_path)
    order = []

    def holder():
        with blob_lock(d, "tok"):
            order.append("a-in")
            time.sleep(0.3)
            order.append("a-out")

    t = threading.Thread(target=holder)
    t.start()
    wait_for(lambda: order == ["a-in"], msg="holder inside")
    with blob_lock(d, "tok", poll_s=0.01) as lk:
        order.append("b-in")
    t.join()
    assert order == ["a-in", "a-out", "b-in"]
    assert lk["waited_s"] > 0.1 and lk["steals"] == 0


def test_blob_lock_steals_dead_pid(tmp_path):
    d = str(tmp_path)
    corpse = subprocess.Popen(["sleep", "0"])
    corpse.wait()
    lock_file = _blob_path(d, "tok") + ".lock"
    with open(lock_file, "w") as f:
        json.dump({"pid": corpse.pid, "key": "tok",
                   "time": time.time()}, f)
    with blob_lock(d, "tok", poll_s=0.01) as lk:
        assert lk["steals"] >= 1            # dead holder reclaimed
    assert not os.path.exists(lock_file)


def test_blob_lock_respects_live_holder_then_times_out(tmp_path):
    d = str(tmp_path)
    lock_file = _blob_path(d, "tok") + ".lock"
    with open(lock_file, "w") as f:         # held by THIS live process
        json.dump({"pid": os.getpid(), "key": "tok",
                   "time": time.time()}, f)
    with pytest.raises(LockTimeout):
        with blob_lock(d, "tok", poll_s=0.02, timeout_s=0.2,
                       stale_s=100.0):
            pass
    assert os.path.exists(lock_file)        # never stolen from the living
    os.unlink(lock_file)


def test_blob_lock_steals_aged_lock(tmp_path):
    d = str(tmp_path)
    lock_file = _blob_path(d, "tok") + ".lock"
    with open(lock_file, "w") as f:         # live PID but ancient
        json.dump({"pid": os.getpid(), "key": "tok",
                   "time": time.time() - 3600.0}, f)
    with blob_lock(d, "tok", stale_s=1.0, poll_s=0.01) as lk:
        assert lk["steals"] >= 1


# ---------------------------------------------------------------------------
# WorkerPool against the stub worker
# ---------------------------------------------------------------------------
def test_pool_roundtrip_and_identity():
    with stub_pool(2) as pool:
        assert pool.wait_ready(20.0)
        imgs = [np.full((2, 2), i, np.int64) for i in range(8)]
        futs = [pool.submit({"n": 2}, im) for im in imgs]
        outs = [f.result(timeout=20) for f in futs]
        for i, out in enumerate(outs):
            assert np.array_equal(out, 2 * imgs[i])
        report = pool.healthz(probe=True)
    assert report["identity_ok"]
    assert report["admitted"] == report["delivered"] == 8
    assert pool.verdict() == "OK"
    assert pool.journal.stats()["dispatch"] == 8
    assert pool.journal.stats()["deliver"] == 8
    # both workers actually served (round-robin)
    assert all(w["pid"] for w in report["workers"])


def test_pool_sigkill_replays_then_restarts():
    with stub_pool(2, stub_env={"STUB_DELAY_S": "0.25"},
                   restart_backoff_s=0.1) as pool:
        assert pool.wait_ready(20.0)
        imgs = [np.full((2, 2), i, np.int64) for i in range(6)]
        futs = [pool.submit({"n": 2}, im) for im in imgs]
        time.sleep(0.05)                    # let dispatch begin
        assert pool.kill_worker(0)
        outs = [f.result(timeout=30) for f in futs]
        for i, out in enumerate(outs):      # replays are bit-exact
            assert np.array_equal(out, 2 * imgs[i])
        assert pool.replays > 0, "no in-flight request was replayed"
        assert pool.workers_lost == 1
        # the killed worker comes back and serves again
        wait_for(lambda: pool._workers[0].alive, 20.0, "worker restart")
        assert pool.wait_ready(20.0)
        out = pool.submit({"n": 2}, imgs[0]).result(timeout=20)
        assert np.array_equal(out, 2 * imgs[0])
        assert pool.worker_restarts >= 1
    assert pool.identity_ok()
    assert pool.failed == 0
    assert pool.verdict() == "WARN"         # loss+replay degrade, not FAIL
    j = pool.journal.stats()
    assert j["replay"] > 0 and j["lost"] == 0


def test_pool_single_worker_loss_is_typed_worker_lost():
    with stub_pool(1, stub_env={"STUB_DELAY_S": "0.4"},
                   max_restarts=0) as pool:
        assert pool.wait_ready(20.0)
        futs = [pool.submit({"n": 2}, np.ones((2, 2), np.int64))
                for _ in range(3)]
        time.sleep(0.05)
        assert pool.kill_worker(0)
        with pytest.raises(WorkerLost):
            futs[0].result(timeout=20)
        for f in futs[1:]:                  # every future resolves typed
            with pytest.raises(WorkerLost):
                f.result(timeout=20)
    assert pool.rejected.get("worker_lost") == 3
    assert pool.identity_ok() and pool.pending() == 0
    assert pool.journal.stats()["lost"] == 3
    assert pool.verdict() == "WARN"


def test_pool_crash_exit_detected_without_external_kill():
    # the stub hard-exits itself mid-service: reader EOF is the crash
    # detector, no signal involved
    with stub_pool(2, stub_env={"STUB_EXIT_AFTER": "2",
                                "STUB_DELAY_S": "0.05"},
                   restart_backoff_s=0.1) as pool:
        assert pool.wait_ready(20.0)
        futs = [pool.submit({"n": 2}, np.ones((2, 2), np.int64))
                for _ in range(10)]
        done = 0
        for f in futs:
            try:
                f.result(timeout=30)
                done += 1
            except ServiceError:
                pass
        assert done > 0
        assert pool.workers_lost >= 1
    assert pool.identity_ok() and pool.failed == 0


def test_pool_pending_budget_rejects_with_retry_hint():
    with stub_pool(1, stub_env={"STUB_DELAY_S": "0.3"},
                   pending_cap=3) as pool:
        assert pool.wait_ready(20.0)
        futs, hints = [], []
        for _ in range(8):
            try:
                futs.append(pool.submit({"n": 2},
                                        np.ones((2, 2), np.int64)))
            except QueueFull as e:
                hints.append(e.retry_after_s)
        assert len(futs) == 3 and len(hints) == 5
        assert all(h is not None and h > 0 for h in hints)
        for f in futs:
            f.result(timeout=20)
    assert pool.rejected_admission.get("queue_full") == 5
    assert pool.identity_ok()
    assert pool.verdict() == "WARN"


def test_pool_typed_error_passthrough_with_hint():
    with stub_pool(1) as pool:
        assert pool.wait_ready(20.0)
        fut = pool.submit({"n": 2, "stub_error": "queue_full",
                           "retry_after_s": 1.5},
                          np.ones((2, 2), np.int64))
        with pytest.raises(QueueFull) as ei:
            fut.result(timeout=20)
        assert ei.value.retry_after_s == 1.5
    assert pool.rejected.get("queue_full") == 1
    assert pool.identity_ok()


def test_pool_probe_suspect_kill_of_hung_worker():
    # worker answers its first frame then goes mute (hung, not dead):
    # the probe monitor must suspect it and kill it
    with stub_pool(1, stub_env={"STUB_MUTE_AFTER": "1"},
                   probe_interval_s=0.05, probe_misses=2,
                   max_restarts=0) as pool:
        pool.wait_ready(5.0)                # first (only) reply
        wait_for(lambda: pool.suspect_kills >= 1, 15.0,
                 "suspect kill of the mute worker")
    assert pool.workers_lost >= 1
    assert pool.verdict() == "WARN"


def test_pool_drain_flushes_in_flight():
    pool = stub_pool(2, stub_env={"STUB_DELAY_S": "0.15"})
    pool.start()
    assert pool.wait_ready(20.0)
    imgs = [np.full((2, 2), i, np.int64) for i in range(4)]
    futs = [pool.submit({"n": 2}, im) for im in imgs]
    pool.drain()                            # graceful: flush, then exit
    for i, f in enumerate(futs):
        assert f.done(), "drain left a future unresolved"
        try:
            assert np.array_equal(f.result(), 2 * imgs[i])
        except ServiceError:
            pass                            # typed shutdown is legal too
    assert pool.identity_ok() and pool.pending() == 0
    assert pool.failed == 0
    with pytest.raises(ServiceError):
        pool.submit({"n": 2}, imgs[0])      # drained pool admits nothing


# ---------------------------------------------------------------------------
# real router workers (slow tier: each spawn pays the jax import)
# ---------------------------------------------------------------------------
REPO = os.path.dirname(HERE)
SRC = os.path.join(REPO, "src")


def worker_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
def test_jsonl_sigterm_drains_and_emits_final_healthz(tmp_path):
    cmd = [sys.executable, "-m", "repro.launch.serve", "--mode", "service",
           "--jsonl", "--sigterm-drain", "--batch", "2",
           "--manifest", '[{"n": 5}]', "--aot-dir", str(tmp_path)]
    proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True,
                            env=worker_env())
    try:
        img = np.ones((5, 5), np.int32)
        proc.stdin.write(json.dumps(
            {"op": "submit", "id": "r1", "n": 5,
             "data": img.tolist()}) + "\n")
        proc.stdin.flush()
        first = json.loads(proc.stdout.readline())
        assert first["id"] == "r1" and first["ok"]
        proc.send_signal(signal.SIGTERM)
        rest = [json.loads(line) for line in proc.stdout
                if line.strip()]
        rc = proc.wait(timeout=60)
    finally:
        proc.kill()
    assert rc == 0, "SIGTERM must drain, not kill the worker"
    finals = [m for m in rest if m.get("id") == "__drain__"]
    assert finals and finals[-1].get("final") is True
    assert finals[-1]["verdict"] in ("OK", "WARN")
    assert finals[-1]["stats"]["pending"] == 0


@pytest.mark.slow
def test_cross_process_compile_coalescing_and_stale_lock(tmp_path):
    """Two fresh worker processes cold-start one aot_dir concurrently:
    exactly one compile per unique cache token (the file locks coalesce
    them); a third worker then recovers past stale dead-PID locks."""
    aot = str(tmp_path / "aot")
    cmd = [sys.executable, "-m", "repro.launch.serve", "--mode", "service",
           "--jsonl", "--framed", "--batch", "2",
           "--manifest", '[{"n": 5}]', "--aot-dir", aot]

    def spawn():
        return subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True,
                                env=worker_env())

    def healthz(proc):
        write_frame(proc.stdin, {"op": "healthz", "id": "h"})
        while True:
            msg = read_frame(proc.stdout)
            assert msg is not None, "worker died before healthz reply"
            if msg.get("id") == "h":
                return msg

    def shutdown(proc):
        write_frame(proc.stdin, {"op": "shutdown", "id": "bye"})
        assert proc.wait(timeout=60) == 0

    p1, p2 = spawn(), spawn()               # genuinely concurrent boot
    try:
        h1, h2 = healthz(p1), healthz(p2)
        shutdown(p1)
        shutdown(p2)
    finally:
        p1.kill()
        p2.kill()
    blobs = list_blobs(aot)
    assert blobs, "cold start published no executables"
    misses = h1["persistent"]["misses"] + h2["persistent"]["misses"]
    hits = h1["persistent"]["hits"] + h2["persistent"]["hits"]
    assert misses == len(blobs), \
        (f"coalescing broken: {misses} compiles for {len(blobs)} "
         f"unique executables ({h1['persistent']} / {h2['persistent']})")
    assert hits == len(blobs), "the non-compiling worker must restore"
    assert not [f for f in os.listdir(aot) if f.endswith(".lock")]

    # stale dead-PID locks on every blob: a fresh worker must steal
    # them and come up warm, not deadlock or recompile
    corpse = subprocess.Popen(["sleep", "0"])
    corpse.wait()
    for key in blobs:
        with open(_blob_path(aot, key) + ".lock", "w") as f:
            json.dump({"pid": corpse.pid, "key": key,
                       "time": time.time() - 3600.0}, f)
    p3 = spawn()
    try:
        h3 = healthz(p3)
        shutdown(p3)
    finally:
        p3.kill()
    assert h3["persistent"]["misses"] == 0
    assert h3["persistent"]["hits"] == len(blobs)
    assert h3["persistent"]["lock_steals"] >= len(blobs)
    assert not [f for f in os.listdir(aot) if f.endswith(".lock")]


@pytest.mark.slow
def test_pool_of_real_workers_end_to_end(tmp_path):
    """A small WorkerPool over two real router workers: bit-exact
    against the in-process oracle, pool healthz aggregates worker
    reports (faults spec echoed), identity closes."""
    import jax.numpy as jnp

    from repro import radon

    aot = str(tmp_path / "aot")
    n = 5
    spec = "sites=dispatch;error_count=1;seed=3"
    env = worker_env()
    env["REPRO_FAULTS"] = spec
    rng = np.random.default_rng(0)
    imgs = [rng.integers(0, 50, (n, n)).astype(np.int32)
            for _ in range(8)]
    fwd = radon.DPRT((1, n, n), jnp.int32)
    expected = [np.asarray(fwd(jnp.asarray(im[None])))[0] for im in imgs]

    pool = WorkerPool(2, aot_dir=aot, manifest=[{"n": n}], max_batch=2,
                      env=env, probe_interval_s=1.0)
    with pool:
        assert pool.wait_ready(600.0), "real workers never became ready"
        futs = [pool.submit({"n": n}, im) for im in imgs]
        outs = [f.result(timeout=300) for f in futs]
        report = pool.healthz(probe=True)
    for out, want in zip(outs, expected):
        assert np.array_equal(np.asarray(out), want)
    assert report["identity_ok"]
    assert report["delivered"] == len(imgs)
    for w in report["workers"]:
        assert w["faults_env"] == spec      # env seam reached the worker
        assert w["retraces_since_start"] == 0
    misses = sum(w["persistent"]["misses"] for w in report["workers"])
    assert misses == len(list_blobs(aot))   # coalesced cold start


def test_pool_of_serve_workers_refused_on_a_chip_host(tmp_path, monkeypatch):
    """A process that runs JAX on a TPU holds the chip, so serve workers
    it would start could not reach it: the pool refuses to start them
    (typed, before any spawn) instead of hanging or crash-looping."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = WorkerPool(2, aot_dir=str(tmp_path), manifest=[{"n": 5}])
    with pytest.raises(RuntimeError, match="one process per chip"):
        pool.start()
    assert all(w.proc is None for w in pool._workers)
    # a worker command that is not a JAX serve worker is not refused
    stub = stub_pool(1)
    with stub:
        assert stub.wait_ready(30.0)
