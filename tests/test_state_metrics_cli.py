"""State API, metrics, timeline, microbenchmark, CLI tests
(SURVEY.md §2.3 state API, §5.1 tracing, §5.5 metrics, §4 microbenchmark)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_lib
from ray_tpu.util import state


# ---------------------------------------------------------------- state API

def test_list_and_summaries(ray_start_regular):
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    ray_tpu.get(a.ping.remote())
    ref = ray_tpu.put(np.arange(100))

    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    actors = state.list_actors(state="ALIVE")
    assert len(actors) == 1 and actors[0]["class_name"] == "A"
    objs = state.list_objects()
    assert any(o["object_id"] == str(ref.id) for o in objs)
    workers = state.list_workers()
    assert len(workers) >= 1

    summ = state.cluster_summary()
    assert summ["nodes"] == 1
    assert summ["objects"]["count"] >= 1
    assert "CPU" in summ["resources_total"]

    mem = state.object_memory()
    assert sum(g["count"] for g in mem) >= 1


def test_object_memory_groups(ray_start_regular):
    small = ray_tpu.put(b"x" * 1000)          # slab
    big = ray_tpu.put(np.zeros(500_000))      # shm file plane (4MB)
    rows = state.object_memory(group_by="loc")
    locs = {r["loc"] for r in rows}
    assert "shm" in locs
    assert ("slab" in locs) or ("inline" in locs)
    del small, big


# ------------------------------------------------------------------ metrics

def test_metrics_counter_gauge_histogram():
    metrics_lib._reset_for_tests()
    c = metrics_lib.Counter("req_total", "requests", ("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    g = metrics_lib.Gauge("queue_len")
    g.set(7)
    h = metrics_lib.Histogram("latency_s", boundaries=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    snap = metrics_lib.registry_snapshot()
    assert snap["req_total"]["kind"] == "counter"
    series = {tuple(sorted(s["tags"].items())): s["value"]
              for s in snap["req_total"]["series"]}
    assert series[(("route", "/a"),)] == 3.0
    assert snap["latency_s"]["series"][0]["value"]["count"] == 3

    text = metrics_lib.prometheus_text()
    assert 'req_total{route="/a"} 3.0' in text
    assert "# TYPE latency_s histogram" in text
    assert 'latency_s_bucket{le="+Inf"} 3' in text
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        metrics_lib.Gauge("req_total")  # kind clash


def test_metrics_cluster_publish(ray_start_regular):
    metrics_lib._reset_for_tests()
    metrics_lib.Gauge("driver_gauge").set(1.0)
    metrics_lib.publish()

    @ray_tpu.remote
    def worker_side():
        from ray_tpu.util import metrics as m
        m._reset_for_tests()
        m.Counter("worker_counter").inc(5)
        m.publish()
        return True

    assert ray_tpu.get(worker_side.remote())
    merged = metrics_lib.collect_cluster()
    assert "driver_gauge" in merged and "worker_counter" in merged


# ------------------------------------------------------------- TSDB history

def test_metrics_history_and_top_cli(capsys):
    """`ray_tpu top` renders LIVE data from a real cluster: worker
    publishers feed the head TSDB, state.metrics_history() answers
    windowed queries over it, and one `top --once` frame shows the
    task-rate row computed from that history."""
    from conftest import time_scale

    ray_tpu.init(num_cpus=2,
                 _system_config={"metrics_export_period_s": 1.0})
    try:
        @ray_tpu.remote
        def tick(x):
            return x + 1

        # spread the work over several publish cycles so the counter
        # history actually grows inside the TSDB window
        rate_rows = []
        deadline = time.monotonic() + 45 * time_scale()
        while time.monotonic() < deadline:
            ray_tpu.get([tick.remote(i) for i in range(4)])
            rate_rows = state.metrics_history(
                'sum(rate(rtpu_tasks_total[60s]))')
            if rate_rows and rate_rows[0]["value"] > 0:
                break
            time.sleep(1.0)
        assert rate_rows and rate_rows[0]["value"] > 0, rate_rows

        # range form: the sparkline feed has timestamped points (steps
        # that predate the history are simply absent, not zero-filled)
        end = time.time()
        rng = state.metrics_history('sum(rate(rtpu_tasks_total[60s]))',
                                    start=end - 60, end=end, step=5)
        assert rng and rng[0]["points"]
        assert all(len(p) == 2 and end - 65 <= p[0] <= end + 5
                   for p in rng[0]["points"])

        # series listing carries the worker tag injected at ingest
        series = state.metrics_series("rtpu_tasks_total")
        assert series and all(s["tags"].get("worker") for s in series)

        from ray_tpu.scripts import cli
        rc = cli.main(["top", "--once"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ray_tpu top" in out and "tsdb" in out
        tasks_line = next(ln for ln in out.splitlines()
                          if ln.startswith("tasks"))
        assert float(tasks_line.split("/s")[0].split()[-1]) > 0
    finally:
        ray_tpu.shutdown()
        from ray_tpu._private.config import GLOBAL_CONFIG
        with GLOBAL_CONFIG._lock:
            GLOBAL_CONFIG._overrides.pop("metrics_export_period_s", None)


def test_dashboard_history_endpoint(ray_start_regular):
    """/metrics/history serves TSDB range queries as JSON (the UI's
    sparkline feed); bad input answers 400, not 500."""
    import urllib.error
    import urllib.parse
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    srv = start_dashboard(port=0)
    try:
        port = srv.server_address[1]
        expr = urllib.parse.quote("sum(rate(rtpu_tasks_total[60s]))")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics/history?series={expr}"
                f"&window=120&step=15", timeout=30) as r:
            doc = json.loads(r.read())
        assert doc["expr"] == "sum(rate(rtpu_tasks_total[60s]))"
        assert doc["window_s"] == 120.0 and "results" in doc
        for bad in ("/metrics/history",
                    "/metrics/history?series=rate(broken",
                    "/metrics/history?series=x&window=nan2",
                    "/metrics/history?series=x&step=0"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{bad}", timeout=30)
            assert ei.value.code == 400
    finally:
        stop_dashboard()


# ----------------------------------------------------------------- timeline

def test_timeline_chrome_trace(ray_start_regular, tmp_path):
    @ray_tpu.remote
    def work():
        time.sleep(0.01)
        return 1

    ray_tpu.get([work.remote() for _ in range(3)])
    out = tmp_path / "trace.json"
    deadline = time.time() + 10
    while True:
        # profile events are shipped asynchronously from workers; poll
        events = ray_tpu.timeline(filename=str(out))
        if len(events) >= 3 or time.time() > deadline:
            break
        time.sleep(0.2)
    assert len(events) >= 3
    trace = json.loads(out.read_text())
    # chrome://tracing format: list of events with ph/ts/pid/name
    assert isinstance(trace, list) and trace
    assert {"name", "ph", "ts", "pid"} <= set(trace[0])


# ---------------------------------------------------------------------- CLI

def _cli(*argv, timeout=240, **env):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", *argv],
        capture_output=True, text=True, timeout=timeout, cwd="/root/repo",
        env={**os.environ, **env})


def test_cli_version():
    r = _cli("version")
    assert r.returncode == 0
    assert r.stdout.strip() == ray_tpu.__version__


def test_cli_microbenchmark_quick():
    r = _cli("microbenchmark", "--quick", timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tasks: submit+get throughput" in r.stdout
    assert "put: 8KB objects" in r.stdout


def test_cli_start_status_stop():
    """``start`` waits for the head it forked by reading ``session_latest``,
    and ``status --address auto`` and ``stop`` resolve the same link: under
    the sessions root every test shares, the latest session is whichever
    test of the six xdist workers started a cluster last (``start`` then
    never sees its own pid, ``status`` dials a cluster that is shutting
    down, ``stop`` signals somebody else's head).  The three calls get a
    root of their own, and the status line is waited for until the head
    answers, not asked for once."""
    import shutil
    import tempfile

    from conftest import time_scale

    # not tmp_path: a session's socket paths have 108 characters at most
    sessions = tempfile.mkdtemp(prefix="rtpu_cli_")
    root = {"RTPU_SESSION_DIR_ROOT": sessions}
    r = _cli("start", **root)
    try:
        assert r.returncode == 0, r.stderr[-2000:]
        deadline = time.monotonic() + 60 * time_scale()
        while True:
            r2 = _cli("status", "--address", "auto", **root)
            if r2.returncode == 0 or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        assert r2.returncode == 0, r2.stderr[-2000:]
        summary = json.loads(r2.stdout[r2.stdout.index("{"):])
        assert summary["nodes"] >= 1
    finally:
        r3 = _cli("stop", **root)
        shutil.rmtree(sessions, ignore_errors=True)
        assert r3.returncode == 0, r3.stderr[-2000:]


def test_stack_dump(ray_start_regular):
    """`ray_tpu stack` analog: all-worker thread dumps (SURVEY.md §5.1)."""
    import time as _t

    from ray_tpu._private import worker as _wm

    @ray_tpu.remote
    def sleepy():
        _t.sleep(8)
        return 1

    ref = sleepy.remote()
    # poll until the task is actually ON a worker stack: under host
    # contention dispatch can take seconds, and a dump taken before the
    # task starts legitimately contains no 'sleepy' frame
    deadline = _t.monotonic() + 60
    joined = ""
    expected = 0
    while _t.monotonic() < deadline:
        resp = _wm.global_worker().rpc("stack")
        # expected==0 just means the worker pool hasn't spawned yet on a
        # loaded host — keep polling, don't assert mid-spawn
        expected = max(expected, resp["expected"])
        joined = "\n".join(resp["stacks"].values())
        if "sleepy" in joined or "sleep" in joined:
            break
        _t.sleep(0.3)
    assert expected >= 1
    assert "sleepy" in joined or "sleep" in joined
    ray_tpu.cancel(ref)


def test_debug_stacks_cli(ray_start_regular, tmp_path, capsys):
    """`ray_tpu debug stacks`: the same GCS stack fan-out as
    `ray_tpu stack`, plus a machine-readable -o JSON form."""
    import time as _t

    from ray_tpu._private import worker as _wm
    from ray_tpu.scripts import cli

    @ray_tpu.remote
    def sleepy_cli():
        _t.sleep(8)
        return 1

    ref = sleepy_cli.remote()
    # same poll-until-on-stack discipline as test_stack_dump above
    deadline = _t.monotonic() + 60
    while _t.monotonic() < deadline:
        resp = _wm.global_worker().rpc("stack")
        if resp["expected"] >= 1 and "sleepy_cli" in \
                "\n".join(resp["stacks"].values()):
            break
        _t.sleep(0.3)
    try:
        rc = cli.main(["debug", "stacks"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "===== worker " in out and "sleepy_cli" in out

        path = tmp_path / "stacks.json"
        rc = cli.main(["debug", "stacks", "-o", str(path)])
        capsys.readouterr()
        assert rc == 0
        doc = json.loads(path.read_text())
        assert doc["expected"] >= 1
        assert any("sleepy_cli" in text
                   for text in doc["stacks"].values())
    finally:
        ray_tpu.cancel(ref)


def test_native_store_metrics_exported(ray_start_regular):
    """SURVEY.md §2.1 Stats row: the C++ slab store's own counters
    (shared-header hits/misses/allocs/fails) surface as cluster gauges."""
    import numpy as np

    from ray_tpu.util import metrics

    refs = [ray_tpu.put(np.zeros(20000)) for _ in range(3)]
    _ = ray_tpu.get(refs)
    m = metrics.collect_cluster()
    native = {k: v["series"][0]["value"] for k, v in m.items()
              if k.startswith("rtpu_native_store_")}
    assert native.get("rtpu_native_store_allocs", 0) >= 3
    assert native.get("rtpu_native_store_heap_size", 0) > 0
    # and they render as prometheus text
    text = metrics.prometheus_text(m)
    assert "rtpu_native_store_allocs" in text


def test_device_memory_gauges(monkeypatch):
    """SURVEY.md §5.5: per-chip HBM gauges via PJRT memory_stats, with the
    two documented platform gaps (None stats, cpu devices) handled."""
    import jax

    class FakeDev:
        platform = "tpu"
        id = 3
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_in_use": 123.0, "bytes_limit": 1000.0}

    # the collector only reads devices from an ALREADY-initialized
    # backend (it must never pay PJRT init itself) — initialize the CPU
    # backend so this test passes standalone, not only after other
    # jax-touching tests in the same session
    jax.devices()
    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    out = metrics_lib.device_memory_gauges()
    s = out["rtpu_device_hbm_bytes_in_use"]["series"][0]
    assert s["value"] == 123.0 and s["tags"]["device"] == "3"
    assert out["rtpu_device_hbm_bytes_limit"]["series"][0]["value"] == 1000.0
    # only keys the platform exposes become gauges
    assert "rtpu_device_hbm_peak_bytes" not in out

    class NoStatsDev(FakeDev):
        def memory_stats(self):  # what the CPU client reports
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [NoStatsDev()])
    assert metrics_lib.device_memory_gauges() == {}
