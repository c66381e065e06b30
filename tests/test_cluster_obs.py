"""Cluster-scope telemetry suite (`make t1-cluster-obs`): device-memory
accounting, multi-host metric aggregation, on-demand profiler capture, and
structured access logs (docs/observability.md).

The load-bearing contracts:

- Spool merge: every host spooled under ``BIGDL_OBS_SPOOL_DIR`` rides ONE
  ``/metrics`` scrape with a ``{host=}`` label, ``parse_metrics``
  round-trips every merged row, a torn/corrupt spool line is skipped (never
  fatal), and a dead host degrades to a stale-stamped ``obs_host_up 0`` row
  — the scrape itself never fails. The 2-process gloo drill proves the
  whole loop end to end, including the SIGKILL-one-host degrade.
- A scripted ``obs_spool_write`` failure flips that host to local-only
  metrics, loudly (robustness event + counter), without crashing anything.
- Device memory is absent-not-wrong: a backend without ``memory_stats()``
  yields NO ``device/hbm_*`` gauges rather than fake ones; the pressure
  event fires once per excursion; ``bigdl-tpu top`` renders ``-`` for every
  absent gauge.
- ``/profilez?seconds=N`` captures a ``jax.profiler.trace`` artifact (200),
  409s while one runs, 400s garbage, 503s a scripted capture failure — and
  keeps serving afterwards.
- Every finished serving request lands one access-log record;
  ``to_bdlrec`` re-shards the log into ``.bdlrec`` that StreamingDataSet
  replays with zero record loss and field fidelity.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import cli
from bigdl_tpu.dataset.streaming import StreamingDataSet
from bigdl_tpu.obs import access_log as obs_access_log
from bigdl_tpu.obs import cluster as obs_cluster
from bigdl_tpu.obs import device as obs_device
from bigdl_tpu.obs import exporter
from bigdl_tpu.obs.registry import registry as obs_registry
from bigdl_tpu.utils import faults
from bigdl_tpu.utils.robustness import events

pytestmark = pytest.mark.obs

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


@pytest.fixture(autouse=True)
def _isolate():
    yield
    obs_access_log.reset()
    obs_cluster.reset()
    obs_device.reset()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------- spool merge
class TestSpoolMerge:
    def test_host_lines_round_trip_through_parse_metrics(self, tmp_path,
                                                         monkeypatch):
        obs_registry.reset()
        try:
            obs_registry.counter("reqs").inc(3)
            obs_registry.gauge("train/throughput").set(123.5)
            for v in (1.0, 2.0, 9.0):
                obs_registry.histogram("lat_ms").observe(v)
            assert obs_cluster.SpoolWriter(
                str(tmp_path), host="h0", interval_s=60).write_once()
            obs_registry.gauge("train/throughput").set(77.25)
            assert obs_cluster.SpoolWriter(
                str(tmp_path), host="h1", interval_s=60).write_once()

            monkeypatch.setenv("BIGDL_OBS_SPOOL_DIR", str(tmp_path))
            monkeypatch.setenv("BIGDL_OBS_STALE_S", "3600")
            parsed = exporter.parse_metrics(exporter.render_metrics())
            assert parsed['bigdl_train_throughput{host="h0"}'] \
                == pytest.approx(123.5)
            assert parsed['bigdl_train_throughput{host="h1"}'] \
                == pytest.approx(77.25)
            assert parsed['bigdl_obs_host_up{host="h0"}'] == 1
            assert parsed['bigdl_reqs_total{host="h1"}'] == 3
            assert parsed['bigdl_lat_ms{host="h0",quantile="0.5"}'] \
                == pytest.approx(2.0)
            assert parsed['bigdl_lat_ms_count{host="h0"}'] == 3
            # the round-trip pin: EVERY merged row survives parse_metrics
            # (render once and parse THAT text — host ages tick between
            # renders, so two renders are not comparable row-for-row)
            hosts = obs_cluster.read_spools(str(tmp_path), stale_after_s=3600)
            lines = obs_cluster.render_host_lines(hosts)
            reparsed = exporter.parse_metrics("\n".join(lines))
            for line in lines:
                key, _, val = line.rpartition(" ")
                assert reparsed[key] == pytest.approx(float(val))
            assert set(reparsed) <= set(parsed)   # same keys ride /metrics
        finally:
            obs_registry.reset()

    def test_stale_stamp_corrupt_lines_and_last_valid_wins(self, tmp_path):
        snap = {"counters": {}, "histograms": {},
                "gauges": {"train/throughput": 5.0}}
        path = tmp_path / "host-dead.jsonl"
        with open(path, "wb") as f:
            f.write(obs_cluster._encode_line(
                {"host": "dead", "ts": time.time() - 999, "seq": 6,
                 "snapshot": {"counters": {}, "histograms": {},
                              "gauges": {"train/throughput": 4.0}}}))
            f.write(obs_cluster._encode_line(
                {"host": "dead", "ts": time.time() - 999, "seq": 7,
                 "snapshot": snap}))
            f.write(b'{"torn": tru')            # torn tail, no CRC footer
        # an all-garbage spool is skipped, never fatal
        (tmp_path / "host-junk.jsonl").write_bytes(b"\x00\x01 nope\n")
        hosts = obs_cluster.read_spools(str(tmp_path), stale_after_s=15)
        assert sorted(hosts) == ["dead"]
        assert hosts["dead"]["stale"] is True
        assert hosts["dead"]["seq"] == 7        # last VALID line wins
        assert hosts["dead"]["snapshot"]["gauges"]["train/throughput"] == 5.0
        assert 'bigdl_obs_host_up{host="dead"} 0' \
            in obs_cluster.render_host_lines(hosts)
        table = obs_cluster.host_table(hosts)
        assert table["dead"]["stale"] is True
        assert table["dead"]["throughput"] == 5.0

    def test_spool_write_fault_degrades_to_local_only_loudly(self, tmp_path):
        w = obs_cluster.SpoolWriter(str(tmp_path / "sp"), host="hx",
                                    interval_s=60)
        snap0 = events.snapshot()
        c0 = obs_registry.snapshot()["counters"].get(
            "obs/spool_write_failures", 0)
        with faults.inject_faults("obs_spool_write@1") as plan:
            assert w.write_once() is False
            assert plan.unfired() == []
        assert w.degraded
        assert w.write_once() is False          # local-only from now on
        assert not os.path.exists(w.path)       # nothing half-written
        assert events.deltas(snap0).get("obs_spool_degraded", 0) == 1
        assert obs_registry.snapshot()["counters"][
            "obs/spool_write_failures"] == c0 + 1
        # the process's own metrics plane is untouched: render still works
        assert "bigdl_obs_spool_write_failures_total" \
            in exporter.render_metrics()


# ------------------------------------------------- 2-process gloo drill
class TestClusterDrill:
    def test_two_host_merge_scrape_and_stale_degrade(self, tmp_path):
        """The tier-1 proof: both hosts train under jax.distributed while
        spooling; ONE scrape of process 0's /metrics carries BOTH hosts'
        train/throughput under distinct {host=} labels; SIGKILLing host 1
        stale-stamps its row without ever failing the scrape."""
        port = _free_port()
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)      # workers set their own device count
        env.pop("BIGDL_METRICS_PORT", None)  # worker 0 binds its own port
        env["BIGDL_MH_MODE"] = "obs"
        env["BIGDL_OBS_SPOOL_DIR"] = str(tmp_path / "spool")
        env["BIGDL_OBS_SPOOL_S"] = "0.3"
        env["BIGDL_OBS_STALE_S"] = "2.0"
        env["BIGDL_MH_ITERS"] = "6"

        outs = [str(tmp_path / f"worker{pid}.json") for pid in (0, 1)]
        p1 = subprocess.Popen(
            [sys.executable, _WORKER, str(port), "1", outs[1]],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        env0 = dict(env)
        env0["BIGDL_MH_PEER_PID"] = str(p1.pid)
        p0 = subprocess.Popen(
            [sys.executable, _WORKER, str(port), "0", outs[0]],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env0)
        stdouts = {}
        for name, p in (("p0", p0), ("p1", p1)):
            try:
                stdouts[name], _ = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                p0.kill()
                p1.kill()
                pytest.fail(f"obs drill worker {name} timed out")
        assert p0.returncode == 0, f"worker 0 failed:\n{stdouts['p0'][-3000:]}"
        # worker 1 is SIGKILLed mid-idle by worker 0 — that IS the drill
        assert p1.returncode == -9, (p1.returncode, stdouts["p1"][-2000:])

        with open(outs[1]) as f:
            pl1 = json.load(f)      # written BEFORE the kill
        assert pl1["host"] == "1"
        assert pl1["spool_writes"] >= 1
        with open(outs[0]) as f:
            pl0 = json.load(f)
        assert pl0["scrape_status"] == 200
        assert pl0["throughput_hosts"] == ["0", "1"]
        assert pl0["host_up_initial"] == {"0": 1, "1": 1}
        assert pl0["round_trip_ok"] is True
        # the degrade: host 1 stamped stale, host 0 live, scrape still 200
        assert pl0["stale_stamped"] is True
        assert pl0["scrape_status_after_kill"] == 200
        assert pl0["host0_up_after_kill"] == 1
        assert pl0["statusz_hosts"] == ["0", "1"]
        assert pl0["statusz_host1_stale"] is True
        # virtual CPU devices report no memory_stats — hbm rows are allowed
        # to be absent (absent-not-wrong), but never partial garbage
        assert set(pl0["hbm_hosts"]) <= {"0", "1"}


# ------------------------------------------------------------ device memory
class TestDeviceMemory:
    def test_sample_absent_not_wrong(self):
        obs_registry.reset()
        try:
            out = obs_device.sample_device_memory()
            assert isinstance(out, list)
            gauges = obs_registry.snapshot()["gauges"]
            if out:     # backend reports: aggregate gauges must exist
                assert gauges["device/hbm_bytes_in_use"] \
                    == sum(e["bytes_in_use"] for e in out)
            else:       # backend silent: NO fabricated gauges
                assert "device/hbm_bytes_in_use" not in gauges
                assert "device/hbm_headroom" not in gauges
        finally:
            obs_registry.reset()

    def test_live_buffer_census_counts_held_arrays(self):
        import jax.numpy as jnp
        x = jnp.ones((128, 64), jnp.float32)
        census = obs_device.live_buffer_census(publish=False)
        assert census["count"] >= 1
        assert census["bytes"] >= 128 * 64 * 4
        assert "float32" in census["by_dtype"]
        del x

    def test_program_memory_attribution_absent_ok(self):
        import jax
        import jax.numpy as jnp
        fn = jax.jit(lambda a, b: (a @ b).sum())
        args = (jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32))
        pm = obs_device.program_memory(fn, *args)
        # CPU XLA may or may not expose memory_analysis(); either way the
        # call never raises and never returns fabricated fields
        assert pm is None or (
            pm and all(isinstance(v, int) and v >= 0 for v in pm.values()))

    def test_pressure_event_fires_once_per_excursion(self):
        mon = obs_device.DeviceMonitor(interval_s=60, pressure_pct=10.0)
        snap0 = events.snapshot()
        low = [{"id": 0, "headroom": 0.02}]
        mon._check_pressure(low)
        mon._check_pressure(low)                # still in the same excursion
        assert events.deltas(snap0).get("hbm_pressure", 0) == 1
        mon._check_pressure([{"id": 0, "headroom": 0.5}])   # recovers
        mon._check_pressure(low)                # new excursion
        assert events.deltas(snap0).get("hbm_pressure", 0) == 2

    def test_monitor_stats_block_shape(self):
        mon = obs_device.DeviceMonitor(interval_s=60)
        mon.poll_once()
        assert mon.polls == 1
        st = obs_device.stats()
        assert set(st) == {"devices", "live_buffers"}
        assert isinstance(st["devices"], list)
        mon.stop()


# --------------------------------------------------------- profiler capture
class TestProfilez:
    def test_capture_routes_and_cli(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TRACE_DIR", str(tmp_path))
        srv = exporter.MetricsExporter(0).start()
        try:
            with urllib.request.urlopen(
                    srv.url + "/profilez?seconds=0.05", timeout=60) as r:
                assert r.status == 200
                payload = json.loads(r.read())
            assert payload["artifact"].startswith(str(tmp_path))
            assert os.path.isdir(payload["artifact"])

            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    srv.url + "/profilez?seconds=nope", timeout=10)
            assert ei.value.code == 400

            monkeypatch.setattr(exporter, "_PROFILE_BUSY", True)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    srv.url + "/profilez?seconds=0.05", timeout=10)
            assert ei.value.code == 409
            monkeypatch.setattr(exporter, "_PROFILE_BUSY", False)

            with faults.inject_faults("profilez_capture@1") as plan:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        srv.url + "/profilez?seconds=0.05", timeout=10)
                assert ei.value.code == 503
                assert plan.unfired() == []
            # the endpoint (and the process it observes) keeps serving
            with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
                assert r.status == 200

            # `bigdl-tpu prof` — the CLI form of the same route
            ns = argparse.Namespace(host="127.0.0.1", port=srv.port,
                                    seconds=0.05)
            assert cli._run_prof(ns) == 0
        finally:
            srv.stop()


# -------------------------------------------------------------- access log
class TestAccessLog:
    def test_rotation_and_bdlrec_replay_zero_loss(self, tmp_path,
                                                  monkeypatch):
        log_dir, out_dir = str(tmp_path / "alog"), str(tmp_path / "rec")
        monkeypatch.setenv("BIGDL_ACCESS_LOG", log_dir)
        monkeypatch.setenv("BIGDL_ACCESS_LOG_ROTATE_MB", "0.001")  # 4 KB floor
        obs_access_log.reset()
        n = 120
        for i in range(n):
            obs_access_log.log_request(
                trace_id="t%04d" % i, tenant="lm", phase="decode",
                prompt_tokens=8 + i, output_tokens=4, ttft_ms=1.5,
                e2e_ms=9.25, flops=1.0e6,
                outcome="ok" if i % 7 else "timeout")
        log = obs_access_log.from_env()
        assert log.records == n
        assert log.rotations >= 1               # the 4 KB floor forced rolls
        log.close()
        # a torn tail (crashed writer) must be skipped by the converter
        with open(os.path.join(log_dir, "access-torn.jsonl"), "wb") as f:
            f.write(b'{"trace_id": "whole", "outcome": "ok"}\n')
            f.write(b'{"trace_id": "to')
        paths, count = obs_access_log.to_bdlrec(log_dir, out_dir, shards=2)
        assert count == n + 1
        assert len(paths) == 2 and all(os.path.exists(p) for p in paths)

        ds = StreamingDataSet(paths,
                              decoder=obs_access_log.access_record_decoder,
                              shuffle_window=1, num_workers=2, cache=False)
        recs = list(ds.data(train=False))
        ds.close()
        assert len(recs) == count               # zero record loss
        by_id = {r["trace_id"]: r for r in recs}
        assert len(by_id) == count
        # field fidelity on a sampled record
        r = by_id["t0005"]
        assert r["prompt_tokens"] == 13
        assert r["output_tokens"] == 4
        assert r["ttft_ms"] == 1.5
        assert r["e2e_ms"] == 9.25
        assert r["flops"] == 1.0e6
        assert r["outcome"] == "ok"
        assert by_id["t0007"]["outcome"] == "timeout"
        assert by_id["whole"]["outcome"] == "ok"   # the loose hand-written rec
        for rec in recs:
            if rec["trace_id"] != "whole":   # log_request pads FIELDS; the
                assert set(obs_access_log.FIELDS) <= set(rec)  # raw line not

    def test_write_failure_disables_loudly_never_raises(self, tmp_path):
        target = tmp_path / "ro"
        log = obs_access_log.AccessLog(str(target))
        log.log(trace_id="a", outcome="ok")
        assert log.records == 1
        # yank the file out from under the writer: closed handle → write fails
        log._f.close()
        log.log(trace_id="b", outcome="ok")     # must not raise
        assert log.disabled
        log.log(trace_id="c", outcome="ok")     # no-op once disabled
        assert log.records == 1

    def test_unset_env_allocates_nothing(self, monkeypatch):
        monkeypatch.delenv("BIGDL_ACCESS_LOG", raising=False)
        obs_access_log.reset()
        assert obs_access_log.from_env() is None
        obs_access_log.log_request(trace_id="x", outcome="ok")  # free no-op

    def test_engine_completion_paths_write_records(self, tmp_path,
                                                   monkeypatch):
        """Every finished request — completed AND timed out — lands one
        record with the pinned fields, via the real engine paths."""
        from bigdl_tpu.models.transformerlm import TransformerLM
        from bigdl_tpu.serving import ServingEngine

        monkeypatch.setenv("BIGDL_ACCESS_LOG", str(tmp_path / "alog"))
        obs_access_log.reset()
        lm = TransformerLM(50, embed_dim=16, num_heads=2, num_layers=1,
                           max_len=32).evaluate()
        prompt = np.arange(1, 7, dtype=np.int32)
        with ServingEngine(lm, max_len=32, slots=2, buckets=(8,),
                           name="lm") as eng:
            res = eng.submit(prompt, 4).result(timeout=180)
        assert res.n_generated == 4
        log = obs_access_log.from_env()
        log.close()
        with open(log.path) as f:
            recs = [json.loads(line) for line in f if line.strip()]
        ok = [r for r in recs if r["outcome"] == "ok"]
        assert len(ok) == 1
        r = ok[0]
        assert r["tenant"] == "lm"
        assert r["phase"] == "decode"
        assert r["prompt_tokens"] == 6
        assert r["output_tokens"] == 4
        assert r["ttft_ms"] is not None and r["ttft_ms"] >= 0
        assert r["e2e_ms"] is not None and r["e2e_ms"] > 0
        assert r["trace_id"] == res.trace_id


# ------------------------------------------------------------ cli rendering
class TestTopRendering:
    def test_renders_hbm_and_host_columns(self):
        text = "\n".join([
            "bigdl_train_throughput 100.0",
            "bigdl_device_hbm_bytes_in_use 2147483648",
            "bigdl_device_hbm_peak_bytes 3221225472",
            "bigdl_device_hbm_headroom 0.25",
            "bigdl_device_live_buffers 12",
            "bigdl_device_live_buffer_bytes 1048576",
            'bigdl_obs_host_up{host="0"} 1',
            'bigdl_obs_host_age_seconds{host="0"} 0.5',
            'bigdl_train_throughput{host="0"} 100.0',
            'bigdl_device_hbm_bytes_in_use{host="0"} 2147483648',
            'bigdl_obs_host_up{host="1"} 0',
            'bigdl_obs_host_age_seconds{host="1"} 42',
            'bigdl_train_throughput{host="1"} 99.0',
        ])
        frame = cli._render_top(exporter.parse_metrics(text))
        assert "hbm 2.0GB" in frame
        assert "peak 3.0GB" in frame
        assert "headroom 25.0%" in frame
        assert "hosts" in frame
        host_lines = {ln.split()[0]: ln for ln in frame.splitlines()
                      if ln.startswith("    ")}
        assert "up" in host_lines["0"]
        # dead host: stale-stamped, absent hbm renders "-" (never garbage)
        assert "STALE" in host_lines["1"]
        assert "hbm -" in host_lines["1"]

    def test_all_absent_renders_dashes_not_crashes(self):
        frame = cli._render_top({})
        assert "hbm -" in frame
        assert "headroom -" in frame
        assert "hosts" not in frame
