"""``bigdl-tpu`` command-line entry point — the reference's spark-submit /
``scripts/bigdl.sh`` launcher analog (SURVEY.md §2.5 Build system, L8).

The reference launches training through ``spark-submit`` with env setup done by
``bigdl.sh`` and per-app scopt CLIs. TPU-native there is no cluster submitter:
one console script fans out to the model training mains (each keeping its
reference-style argparse options) and the multi-chip dry run.
Environment flags (the ``bigdl.*`` property tier) are plain ``BIGDL_*`` env
vars — see ``conf/bigdl-tpu.conf`` for the reference list.
"""

from __future__ import annotations

import argparse
import sys

# subcommand → (module with main(argv), description)
_TRAIN_MAINS = {
    "lenet": ("bigdl_tpu.models.lenet.train", "LeNet-5 / MNIST"),
    "resnet": ("bigdl_tpu.models.resnet.train", "ResNet CIFAR/ImageNet"),
    "inception": ("bigdl_tpu.models.inception.train", "Inception-v1/v2 ImageNet"),
    "vgg": ("bigdl_tpu.models.vgg.train", "VGG / CIFAR-10"),
    "rnn": ("bigdl_tpu.models.rnn.train", "PTB LSTM language model"),
    "autoencoder": ("bigdl_tpu.models.autoencoder.train", "MNIST autoencoder"),
    "ncf": ("bigdl_tpu.models.ncf.train", "Neural Collaborative Filtering"),
    "widedeep": ("bigdl_tpu.models.widedeep.train", "Wide & Deep recommender"),
    "textclassifier": ("bigdl_tpu.models.textclassifier.train",
                       "temporal-CNN text classification"),
    "treelstm": ("bigdl_tpu.models.treelstm.train", "binary TreeLSTM sentiment"),
    "transformerlm": ("bigdl_tpu.models.transformerlm.train",
                      "decoder-only Transformer LM (flash/ring attention)"),
}


def _run_module(modname: str, argv) -> int:
    import importlib

    mod = importlib.import_module(modname)
    out = mod.main(argv)
    return out if isinstance(out, int) else 0


def _launch_multihost(args) -> int:
    """Spawn args.nnodes processes on THIS host, each a jax.distributed 'node'
    running the chosen train main with --distributed (reference parity: the
    spark-submit / bigdl.sh cluster launch, SURVEY.md §2.5 — one process per
    executor; the local[N] analog).

    The processes share the host's devices, so ``--devices-per-node`` (virtual
    CPU devices per process) is what partitions them. Without it every child
    would try to take every local chip, and a chip belongs to one process:
    more than one node is refused. One process already drives all local chips
    (``bigdl-tpu train`` with DistriOptimizer over ``Engine.mesh()``)."""
    import os
    import socket
    import subprocess
    import sys

    cpu = bool(args.devices_per_node)
    if not cpu and args.nnodes > 1:
        print(f"launch: refusing to start {args.nnodes} processes on this "
              "host's accelerators: each would try to take every local chip, "
              "and a chip belongs to one process. Pass --devices-per-node K "
              "for a virtual CPU mesh, or run one process (`bigdl-tpu train "
              f"{args.model} ...`), which drives all local chips.",
              file=sys.stderr)
        return 2
    port = args.port
    if port == 0:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
    mod, _ = _TRAIN_MAINS[args.model]
    rest = [a for a in args.rest if a != "--"]
    if "--distributed" not in rest:
        rest.append("--distributed")
    backend_arg = "backend='cpu', " if cpu else ""
    code = (
        "import sys\n"
        "from bigdl_tpu.utils.engine import Engine\n"
        f"Engine.init({backend_arg}"
        f"coordinator_address='localhost:{port}', "
        f"node_number={args.nnodes}, process_id=int(sys.argv[1]))\n"
        f"import importlib\n"
        f"importlib.import_module({mod!r}).main(sys.argv[2:])\n")
    procs = []
    for pid in range(args.nnodes):
        env = dict(os.environ)
        if cpu:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count="
                f"{args.devices_per_node}")
            env.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code, str(pid)] + rest, env=env))
    # wait for EVERY process (no short-circuit: an early crash must not
    # orphan the surviving workers), then report the first failure
    rcs = [p.wait() for p in procs]
    return next((rc for rc in rcs if rc), 0)


def _format_trace(ev: dict) -> str:
    """One tail-sampled request trace as an indented span tree."""
    lines = [f"trace {ev.get('trace_id')}  request {ev.get('request_id')}  "
             f"engine {ev.get('engine')}  e2e {ev.get('e2e_ms')}ms  "
             f"generated {ev.get('n_generated')}  "
             f"finish={ev.get('finish')}"]
    for span in ev.get("spans") or []:
        lines.append(f"  {span.get('name', '?'):<14} "
                     f"start {span.get('start_ms'):>10}ms  "
                     f"dur {span.get('dur_ms'):>10}ms")
    return "\n".join(lines)


def _run_diag(path: str, trace_id=None) -> int:
    """Re-render the unified run report from a saved JSONL event log
    (``BIGDL_OBS_LOG``): the LAST ``run_report`` record renders through the
    same formatter the trainer used, so the text matches the live run's
    byte-for-byte. Watchdog dumps and tail-sampled request traces in the log
    are summarized on stderr. With ``trace_id``, skip the report and print
    the matching ``request_trace`` span tree instead (matches the trace ID
    or the request ID — whichever the operator has in hand)."""
    from bigdl_tpu.obs import report as obs_report
    from bigdl_tpu.obs import trace

    try:
        events = trace.read_events(path)
    except OSError as e:
        print(f"diag: cannot read {path}: {e}", file=sys.stderr)
        return 1
    traces = [ev for ev in events if ev.get("kind") == "request_trace"]
    if trace_id is not None:
        hits = [ev for ev in traces
                if ev.get("trace_id") == trace_id
                or ev.get("request_id") == trace_id]
        if not hits:
            print(f"diag: no request_trace matching {trace_id!r} in {path} "
                  f"({len(traces)} traced request(s) in the log)",
                  file=sys.stderr)
            return 1
        for ev in hits:
            print(_format_trace(ev))
        return 0
    report = None
    dumps = 0
    kinds: dict = {}
    for ev in events:
        kind = ev.get("kind")
        kinds[kind] = kinds.get(kind, 0) + 1
        if kind == "run_report":
            report = ev.get("report")
        elif kind == "watchdog_dump":
            dumps += 1
    if report is None:
        print(f"diag: no run_report event in {path} "
              f"(events seen: {kinds or 'none'})", file=sys.stderr)
        return 1
    print(obs_report.format_report(report))
    if dumps:
        print(f"diag: {dumps} watchdog dump(s) in the log — the run stalled; "
              f"thread stacks are in the watchdog_dump records",
              file=sys.stderr)
    if traces:
        slowest = sorted(traces, key=lambda ev: ev.get("e2e_ms") or 0.0,
                         reverse=True)[:3]
        print(f"diag: {len(traces)} tail-sampled request trace(s); slowest:",
              file=sys.stderr)
        for ev in slowest:
            print(f"diag:   trace {ev.get('trace_id')} "
                  f"e2e {ev.get('e2e_ms')}ms finish={ev.get('finish')} "
                  f"(--trace {ev.get('trace_id')} for the span tree)",
                  file=sys.stderr)
    return 0


def _render_top(metrics: dict, health=None) -> str:
    """Pure renderer for ``bigdl-tpu top``: one dashboard frame from a
    parsed ``/metrics`` scrape (``exporter.parse_metrics``) and an optional
    ``/healthz`` payload. Kept side-effect-free so tests can feed it
    canned scrapes."""
    import re

    def g(name, fmt="{:.4g}", default="-"):
        v = metrics.get(name)
        return fmt.format(v) if v is not None else default

    status = (health or {}).get("status", "?")
    wds = (health or {}).get("watchdogs") or []
    armed = sum(1 for w in wds if w.get("armed"))
    head = f"bigdl-tpu top — status {status}"
    if wds:
        head += f" · watchdogs {armed}/{len(wds)} armed"
    slo = (health or {}).get("slo") or {}
    if slo.get("active"):
        head += " · SLO BREACH " + ",".join(
            sorted(b.get("rule", "?") for b in slo["active"]))
    lines = [head]
    lines.append(
        "  train   mfu " + g("bigdl_train_mfu")
        + "   flops/s " + g("bigdl_train_model_flops_per_sec", "{:.3g}")
        + "   throughput " + g("bigdl_train_throughput", "{:.1f}")
        + "   step p50 " + g('bigdl_train_step_wall{quantile="0.5"}', "{:.4g}")
        + "s   stalls " + g("bigdl_train_feed_stall_total", "{:.0f}", "0"))
    lines.append(
        "  serve   flops/s " + g("bigdl_serve_model_flops_per_sec", "{:.3g}")
        + "   mfu " + g("bigdl_serve_mfu")
        + "   ttft p99 " + g('bigdl_serving_ttft_ms{quantile="0.99"}', "{:.1f}")
        + "ms   e2e p99 " + g('bigdl_serving_e2e_ms{quantile="0.99"}', "{:.1f}")
        + "ms")

    def gb(name):
        # bytes gauge → human-readable, "-" when the backend never said
        v = metrics.get(name)
        if v is None:
            return "-"
        for unit in ("B", "KB", "MB", "GB", "TB"):
            if abs(v) < 1024.0 or unit == "TB":
                return f"{v:.1f}{unit}" if unit != "B" else f"{v:.0f}B"
            v /= 1024.0

    headroom = metrics.get("bigdl_device_hbm_headroom")
    lines.append(
        "  device  hbm " + gb("bigdl_device_hbm_bytes_in_use")
        + "   peak " + gb("bigdl_device_hbm_peak_bytes")
        + "   headroom " + (f"{100 * headroom:.1f}%"
                            if headroom is not None else "-")
        + "   live " + g("bigdl_device_live_buffers", "{:.0f}")
        + " (" + gb("bigdl_device_live_buffer_bytes") + ")")
    # cluster view: every {host=}-labelled series from the spool merge
    hosts: dict = {}
    hpat = re.compile(r'^(\w+)\{host="([^"]*)"(?:,[^}]*)?\}$')
    for key, val in metrics.items():
        m = hpat.match(key)
        if m:
            hosts.setdefault(m.group(2), {})[m.group(1)] = val
    if hosts:
        lines.append("  hosts")
        for hid in sorted(hosts):
            h = hosts[hid]

            def hv(name, fmt="{:.4g}"):
                v = h.get(name)
                return fmt.format(v) if v is not None else "-"

            state = ("STALE" if h.get("bigdl_obs_host_up") == 0.0 else "up"
                     if h.get("bigdl_obs_host_up") is not None else "-")
            lines.append(
                f"    {hid:<12} {state:<6}"
                f" age {hv('bigdl_obs_host_age_seconds', '{:.0f}')}s"
                f"  thr {hv('bigdl_train_throughput', '{:.1f}')}"
                f"  mfu {hv('bigdl_train_mfu')}"
                f"  hbm {hv('bigdl_device_hbm_bytes_in_use', '{:.3g}')}"
                f"  headroom {hv('bigdl_device_hbm_headroom')}")
    tenants: dict = {}
    pat = re.compile(r'^bigdl_serving_tenant_(\w+)\{tenant="([^"]*)"\}$')
    for key, val in metrics.items():
        m = pat.match(key)
        if m:
            tenants.setdefault(m.group(2), {})[m.group(1)] = val
    if tenants:
        lines.append("  tenants")
        engs = (health or {}).get("engines") or {}
        for name in sorted(tenants):
            t = tenants[name]
            state = engs.get(name, {}).get("health", "?")
            if t.get("slo_degraded"):
                state += "/SLO"
            # paged engines report a live used/free page split; slot-grid
            # engines export 0/0 and render "-"
            pages = (f"{t.get('pages_used', 0):.0f}"
                     f"/{t.get('pages_free', 0):.0f}"
                     if t.get("pages_used", 0) or t.get("pages_free", 0)
                     else "-")
            lines.append(
                f"    {name:<12} {state:<10}"
                f" v{t.get('model_version', 0):.0f}"
                f" backlog {t.get('backlog', 0):.0f}"
                f" active {t.get('active_slots', 0):.0f}"
                f" done {t.get('completed', 0):.0f}"
                f" timeouts {t.get('timeouts', 0):.0f}"
                f" shed {t.get('shed', 0):.0f}"
                f" tps {t.get('decode_tps', 0):.1f}"
                f" pages {pages}")
    fleets: dict = {}
    fpat = re.compile(r'^bigdl_fleet_(\w+)\{fleet="([^"]*)"\}$')
    rpat = re.compile(
        r'^bigdl_fleet_replica_(\w+)\{fleet="([^"]*)",replica="([^"]*)"\}$')
    for key, val in metrics.items():
        m = fpat.match(key)
        if m:
            fleets.setdefault(m.group(2), {"replicas": {}})[m.group(1)] = val
    replicas: dict = {}
    for key, val in metrics.items():
        m = rpat.match(key)
        if m:
            replicas.setdefault(
                (m.group(2), m.group(3)), {})[m.group(1)] = val
    for (fname, rname), r in replicas.items():
        fleets.setdefault(fname, {"replicas": {}})["replicas"][rname] = r
    if fleets:
        hfleets = (health or {}).get("fleets") or {}
        for fname in sorted(fleets):
            f = fleets[fname]
            lines.append(
                f"  fleet {fname}"
                f" · healthy {f.get('healthy_replicas', 0):.0f}"
                f"/{len(f['replicas']) or f.get('healthy_replicas', 0):.0f}"
                f" · dispatched {f.get('dispatched', 0):.0f}"
                f" retries {f.get('retries', 0):.0f}"
                f" downs {f.get('replica_downs', 0):.0f}"
                f" rejected {f.get('rejected', 0):.0f}")
            hreps = (hfleets.get(fname) or {}).get("replicas") or {}
            for rname in sorted(f["replicas"]):
                r = f["replicas"][rname]
                state = hreps.get(rname, "?")
                lines.append(
                    f"    {rname:<12} {state:<10}"
                    f" queue {r.get('queue_depth', 0):.0f}"
                    f" active {r.get('active_slots', 0):.0f}"
                    f" done {r.get('completed', 0):.0f}"
                    f" shed {r.get('shed', 0):.0f}"
                    f" wait {r.get('est_wait_ms', 0):.0f}ms"
                    f" tps {r.get('decode_rate', 0):.1f}")
    return "\n".join(lines)


def _run_prof(args) -> int:
    """``bigdl-tpu prof``: the CLI form of ``/profilez`` — ask the running
    process for a ``jax.profiler.trace`` capture of ``--seconds`` and print
    the artifact path. The request blocks for the capture duration; a 409
    means another capture is already running."""
    import json
    import urllib.error
    import urllib.request

    url = (f"http://{args.host}:{args.port}/profilez"
           f"?seconds={args.seconds:g}")
    try:
        with urllib.request.urlopen(url,
                                    timeout=args.seconds + 30.0) as r:
            payload = json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            detail = json.loads(e.read().decode()).get("error", "")
        except Exception:
            detail = ""
        print(f"prof: capture failed (HTTP {e.code}): {detail}",
              file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001 — connection errors end the run
        print(f"prof: cannot reach {url}: {e}", file=sys.stderr)
        return 1
    print(payload.get("artifact", ""))
    return 0


def _run_top(args) -> int:
    """Live terminal dashboard over the metrics endpoint: scrape
    ``/metrics`` + ``/healthz`` every ``--interval`` seconds and render one
    frame per poll (``--once`` for scripts)."""
    import json
    import time
    import urllib.error
    import urllib.request

    from bigdl_tpu.obs import exporter

    base = f"http://{args.host}:{args.port}"
    first = True
    while True:
        try:
            with urllib.request.urlopen(base + "/metrics", timeout=3.0) as r:
                metrics = exporter.parse_metrics(r.read().decode())
        except Exception as e:  # noqa: BLE001 — any scrape failure is fatal
            print(f"top: cannot scrape {base}/metrics: {e}", file=sys.stderr)
            return 1
        health = None
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=3.0) as r:
                health = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            # 503 (an engine died) still carries the JSON body
            try:
                health = json.loads(e.read().decode())
            except Exception:
                pass
        except Exception:
            pass
        if not first:
            print()
        first = False
        print(_render_top(metrics, health))
        if args.once:
            return 0
        time.sleep(args.interval)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    import os as _os
    if _os.environ.get("BIGDL_TRACE"):
        # tracing runs print their active obs configuration up front so the
        # artifact paths are known before any training output scrolls by
        from bigdl_tpu.obs import describe_config
        print(describe_config(), file=sys.stderr)
    # converge forwards option-style args; argparse REMAINDER cannot capture
    # a leading option (py3.12), so hand the tail to its CLI directly
    if argv[:1] == ["converge"]:
        from bigdl_tpu import convergence
        return convergence.main(argv[1:])
    p = argparse.ArgumentParser(
        prog="bigdl-tpu",
        description="TPU-native BigDL: train models, validate multi-chip "
                    "sharding")
    sub = p.add_subparsers(dest="command")

    train = sub.add_parser("train", help="run a model training main")
    train.add_argument("model", choices=sorted(_TRAIN_MAINS))
    train.add_argument("rest", nargs=argparse.REMAINDER,
                       help="arguments forwarded to the model's own CLI")

    sub.add_parser("converge", help="accuracy-parity harness: train a "
                                    "BASELINE config on real data and judge "
                                    "the final metric against its target")
    dry = sub.add_parser("dryrun-multichip",
                         help="compile+run one sharded step on an n-device mesh")
    dry.add_argument("-n", "--n-devices", type=int, default=8)
    sub.add_parser("models", help="list available training mains")
    sub.add_parser("env", help="print the BIGDL_* environment flags in effect")

    diag = sub.add_parser(
        "diag", help="re-render the unified run report from a saved JSONL "
                     "event log (BIGDL_OBS_LOG / docs/observability.md)")
    diag.add_argument("jsonl", help="path to the JSONL event log")
    diag.add_argument("--trace", default=None, metavar="ID",
                      help="print the tail-sampled span tree for one request "
                           "(trace ID or request ID) instead of the report")

    top = sub.add_parser(
        "top", help="live dashboard over a running process's metrics "
                    "endpoint (/metrics + /healthz; BIGDL_METRICS_PORT)")
    top.add_argument("--host", default="127.0.0.1")
    top.add_argument("--port", type=int,
                     default=int(_os.environ.get("BIGDL_METRICS_PORT") or 0),
                     help="exporter port (default: $BIGDL_METRICS_PORT)")
    top.add_argument("--interval", type=float, default=2.0,
                     help="seconds between frames")
    top.add_argument("--once", action="store_true",
                     help="render one frame and exit (for scripts)")

    prof = sub.add_parser(
        "prof", help="trigger an on-demand jax.profiler capture on a "
                     "running process via its /profilez endpoint and print "
                     "the artifact path")
    prof.add_argument("--host", default="127.0.0.1")
    prof.add_argument("--port", type=int,
                      default=int(_os.environ.get("BIGDL_METRICS_PORT") or 0),
                      help="exporter port (default: $BIGDL_METRICS_PORT)")
    prof.add_argument("--seconds", type=float, default=2.0,
                      help="capture duration")

    launch = sub.add_parser(
        "launch", help="spawn an N-process jax.distributed training run on "
                       "this host (the spark-submit analog; each process = "
                       "one 'node')")
    launch.add_argument("-n", "--nnodes", type=int, default=2)
    launch.add_argument("--port", type=int, default=0,
                        help="coordinator port (0 = pick a free one)")
    launch.add_argument("--devices-per-node", type=int, default=None,
                        help="virtual CPU devices per process; required "
                        "when -n > 1 (processes on one host cannot share "
                        "its chips)")
    launch.add_argument("model", choices=sorted(_TRAIN_MAINS))
    launch.add_argument("rest", nargs=argparse.REMAINDER,
                        help="arguments forwarded to the model's own CLI")

    args = p.parse_args(argv)
    if args.command == "diag":
        return _run_diag(args.jsonl, trace_id=args.trace)
    if args.command == "top":
        if not args.port:
            print("top: no exporter port — pass --port or set "
                  "BIGDL_METRICS_PORT", file=sys.stderr)
            return 2
        return _run_top(args)
    if args.command == "prof":
        if not args.port:
            print("prof: no exporter port — pass --port or set "
                  "BIGDL_METRICS_PORT", file=sys.stderr)
            return 2
        return _run_prof(args)
    if args.command == "train":
        mod, _ = _TRAIN_MAINS[args.model]
        return _run_module(mod, args.rest)
    if args.command == "launch":
        return _launch_multihost(args)
    if args.command == "dryrun-multichip":
        from bigdl_tpu import dryrun
        dryrun.dryrun_multichip(args.n_devices)
        return 0
    if args.command == "models":
        for name, (_, desc) in sorted(_TRAIN_MAINS.items()):
            print(f"  {name:<16} {desc}")
        return 0
    if args.command == "env":
        import os
        for key in sorted(k for k in os.environ if k.startswith("BIGDL_")):
            print(f"{key}={os.environ[key]}")
        return 0
    p.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
