#!/usr/bin/env python3
"""One run of one benchmark cell: the node daemon's own ring-serving loop
on the chip, fed open-loop by a producer process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<name>.json``: the rule table
and the daemon's flags) and a traffic mix (``traffic/<name>.json``);
``BENCHMARK.json`` at the root of the checkout lists both and the
metrics, each read by ``metrics/<name>.py``.  Set-up builds the daemon
as ``python -m infw.daemon`` does, loads the seeded table through the
NodeState file protocol, starts the daemon's loop, and warms it with
every pooled record of the cell's traffic.  The window then runs for
``--seconds``; afterwards every delivered verdict and the statistics
delta are compared with the plain reference (``reference.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and ``checks`` last: each compared number with its
limit.  The run refuses anything but a TPU, and exits non-zero with no
result line when a check cannot be made.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, trace  # noqa: E402
from benchmark.traffic import _gen  # noqa: E402

NODE = "bench-node"
#: seconds after the window closes that a pushed record may still take
GRACE_S = 60.0
#: length of the profiled stretch that ends with the window (--trace 1)
TRACE_S = 4.0
#: harness spans that name the device's idle gaps, in priority order
SINK_SPAN, PASS_SPAN = "bench.verdict_sink", "bench.loop_pass"
IDLE_LABELS = (("verdict sink", SINK_SPAN), ("loop pass", PASS_SPAN))
IDLE_OTHER = "poll sleep"


class RunError(RuntimeError):
    """The run could not be made; no result is printed."""


# -- what the cell is ---------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> SimpleNamespace:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), bench=bench,
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       cell["traffic"] + ".json")),
    )


def cell_metrics(bench: dict, cell: str, traced: bool) -> list:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``traced`` the per-layer metrics that list it (or, without a
    list, that move one of its end-to-end metrics)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_metric(name: str, run) -> float | None:
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


# -- inputs --------------------------------------------------------------------


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2**64 - 1), stream])


def make_inputs(config: dict, traffic: dict, seed: int) -> SimpleNamespace:
    """The table, the interfaces and the pool of records, from the seed."""
    t = config["table"]
    ifaces = {int(k): v for k, v in t["interfaces"].items()}
    # the prefix tree comes from the configuration's own seed and the run's
    # seed relabels it: every seed gets the same device array shapes, so
    # only a checkout's first run compiles
    shape = _gen.random_tables(
        seed_rng(t["shape_seed"], 1), n_entries=t["entries"],
        width=t["rule_width"], v6_fraction=t["v6_fraction"],
        ifindexes=sorted(ifaces), v4_prefix_lens=t["v4_prefix_lens"],
        v6_prefix_lens=t["v6_prefix_lens"], group_size=t["group_size"],
    )
    table = _gen.relabel(seed_rng(seed, 1), shape)
    n = int(traffic["record_packets"]) * int(traffic["pool_records"])
    # every seed gets the same run lengths, in its own order
    rng = seed_rng(seed, 2)
    copies = rng.permutation(_gen.pareto_copies(
        seed_rng(traffic["shape_seed"], 1), n, traffic["pareto_a"],
        traffic["pareto_b"], traffic["max_copies"]))
    pool = _gen.trace_packets(
        rng, table, copies, wildcard_protos=traffic["wildcard_protocols"],
        frame_sizes=traffic["frame_sizes"], frame_weights=traffic["frame_weights"])
    return SimpleNamespace(table=table, ifaces=ifaces, pool=pool,
                           record_packets=int(traffic["record_packets"]),
                           pool_records=int(traffic["pool_records"]))


def pack_pool(inputs, path: str) -> None:
    """Pack each pooled record in the ring's wire layout (the program's
    own packer: the record format is the ring's interface)."""
    from infw.packets import PacketBatch

    p = inputs.pool
    batch = PacketBatch(**{f: getattr(p, f) for f in _gen.Packets.FIELDS})
    n = inputs.record_packets
    arrays, v4_only = {}, []
    for i in range(inputs.pool_records):
        sub = batch.slice(i * n, (i + 1) * n)
        arrays[f"wire_{i}"] = (sub.pack_wire_v4() if sub.is_v4_compactable()
                               else sub.pack_wire())
        v4_only.append(not bool((sub.kind == 2).any()))
    np.savez(path, v4_only=np.asarray(v4_only), **arrays)


# -- the system under test -------------------------------------------------------


class GcMeter:
    """Records the interpreter's garbage collections while it is open:
    (start, end, generation).  A full collection stops every thread of
    the daemon's process, the loop's included."""

    def __init__(self) -> None:
        self.events = []
        self._t = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.events.append((self._t, time.monotonic(), info["generation"]))

    def __enter__(self) -> "GcMeter":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *_exc) -> None:
        gc.callbacks.remove(self._on_gc)


class CompileMeter:
    """Counts the XLA executables built or loaded while it is open."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileMeter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *_exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def build_daemon(argv: list, ifaces: dict):
    """The ``Daemon`` that ``infw.daemon.main(argv)`` builds, caught before
    it starts; only the interface registry is the benchmark's."""
    import infw.daemon as d
    from infw.interfaces import Interface, InterfaceRegistry

    registry = InterfaceRegistry()
    for index, name in ifaces.items():
        registry.add(Interface(name=name, index=index))

    class Built(Exception):
        pass

    made = []
    real = d.Daemon

    def capture(**kw):
        made.append(real(registry=registry, **kw))
        raise Built

    d.Daemon = capture
    try:
        rc = d.main(argv)
    except Built:
        return made[0]
    except SystemExit as e:
        rc = e.code
    finally:
        d.Daemon = real
    raise RunError(f"infw.daemon.main refused {argv}: exit {rc}")


def start_daemon(config: dict, inputs, work: str, traced: bool):
    state = os.path.join(work, "state")
    os.makedirs(state)
    args = [a.replace("{work}", work) for a in config["daemon_args"]]
    argv = ["--state-dir", state, "--node-name", NODE, "--backend", "tpu",
            "--metrics-port", "0", "--health-port", "0", *args]
    if traced:
        argv.append("--trace")
    daemon = build_daemon(argv, inputs.ifaces)
    path = os.path.join(daemon.nodestates_dir, f"{NODE}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(_gen.nodestate_doc(inputs.table, NODE, inputs.ifaces), f)
    os.replace(path + ".tmp", path)
    daemon.scan_nodestates_once()
    clf = daemon.syncer.classifier
    if clf is None or clf.tables is None:
        raise RunError("the NodeState did not load (see the daemon log)")
    if clf.tables.num_entries != len(inputs.table):
        raise RunError(f"{clf.tables.num_entries} entries loaded, "
                       f"{len(inputs.table)} sent")
    if daemon.ingest_ring is None:
        raise RunError("the configuration's daemon_args give no --ring")
    return daemon


class Recorder:
    """Harness spans around the calls into the daemon: each pass of its
    loop through ``process_ring_once`` (wrapped on the instance, so the
    daemon's own loop calls the wrapper) and each verdict delivery."""

    def __init__(self, daemon, annotate: bool, alter=None) -> None:
        self.passes = []      # (start, end, packets)
        self.deliveries = []  # (seq, monotonic time, results)
        self._alter = alter
        self._annotate = annotate
        inner = daemon.process_ring_once

        def process_ring_once(*a, **kw):
            t = time.monotonic()
            if self._annotate:
                import jax

                with jax.profiler.TraceAnnotation(PASS_SPAN):
                    n = inner(*a, **kw)
            else:
                n = inner(*a, **kw)
            self.passes.append((t, time.monotonic(), n))
            return n

        daemon.process_ring_once = process_ring_once
        daemon.ring_verdict_sink = self.sink

    def sink(self, seq, results) -> None:
        t = time.monotonic()
        if self._annotate:
            import jax

            with jax.profiler.TraceAnnotation(SINK_SPAN):
                self._deliver(seq, t, results)
        else:
            self._deliver(seq, t, results)

    def _deliver(self, seq, t, results) -> None:
        if self._alter is not None:
            results = self._alter(seq, results)
        if results is not None:
            self.deliveries.append((int(seq), t, results))


class Producer:
    """The producer child process (``producer.py``); it imports no JAX."""

    def __init__(self, ring: str, pool: str, log: str) -> None:
        self.log = log
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "producer.py"),
             "--ring", ring, "--pool", pool, "--log", log],
            stdin=subprocess.PIPE, text=True,
        )

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def finish(self, timeout: float) -> dict:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise RunError("the producer did not finish")
        if rc != 0:
            raise RunError(f"the producer exited {rc}")
        z = np.load(self.log)
        return {k: z[k] for k in z.files}

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        if self.proc.stdin:
            self.proc.stdin.close()


def wait_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def wait_pass_end(rec, after: float, limit: float) -> None:
    """Wait from ``after`` until a loop pass ends (or until ``limit``)."""
    wait_until(after)
    while time.monotonic() < limit:
        if rec.passes and rec.passes[-1][1] >= after:
            return
        time.sleep(0.002)


def tracer_sums(daemon) -> dict:
    tr = getattr(daemon, "tracer", None)
    if tr is None:
        return {}
    return {s: (v["count"], v["sum_us"]) for s, v in tr.histograms.values().items()}


# -- one run --------------------------------------------------------------------


def run_cell(cell, seed: int, seconds: float, traced: bool, work: str,
             t_begin: float = T_START, alter=None, patch=None,
             warm_timeout: float = 900.0) -> SimpleNamespace:
    """Set-up, warm-up, window and drain of one run.  ``alter(seq,
    results)`` and ``patch(daemon)`` plant faults for the checks' own
    tests and the control; a run of the benchmark passes neither."""
    import jax

    cfg, tfc = cell.config, cell.traffic
    marks = {"start": time.monotonic()}
    inputs = make_inputs(cfg, tfc, seed)
    pool_path = os.path.join(work, "pool.npz")
    pack_pool(inputs, pool_path)
    marks["inputs"] = time.monotonic()
    daemon = start_daemon(cfg, inputs, work, traced)
    marks["table_loaded"] = time.monotonic()
    producer = None
    try:
        if patch is not None:
            patch(daemon)
        rec = Recorder(daemon, annotate=traced, alter=alter)
        clf = daemon.syncer.classifier
        producer = Producer(daemon.ingest_ring.path, pool_path,
                            os.path.join(work, "pushes.npz"))
        stats_before = clf.stats.snapshot()
        with CompileMeter() as meter, GcMeter() as gcm:
            daemon.start()
            producer.send("warm")
            deadline = time.monotonic() + warm_timeout
            while len(rec.deliveries) < inputs.pool_records:
                if time.monotonic() > deadline:
                    raise RunError("the warm-up records were not served")
                time.sleep(0.01)
            warm_compiles = meter.count
            marks["warmed"] = time.monotonic()
            t_start = time.monotonic() + 0.2
            t0 = t_start + float(tfc["lead_s"])
            t1 = t0 + seconds
            setup_s = t_start - t_begin
            producer.send("go " + json.dumps({"t_start": t_start, "t1": t1}))
            wait_until(t0)
            at_t0 = (meter.count, tracer_sums(daemon))
            prof = None
            if traced:
                # profile whole loop passes: from the end of one to the
                # end of another, the last near the window's close
                wait_pass_end(rec, max(t0, t1 - TRACE_S - 0.5), t1)
                jax.profiler.start_trace(os.path.join(work, "trace"))
                prof = [time.monotonic()]
            wait_until(t1)
            at_t1 = (meter.count, tracer_sums(daemon))
            if traced:
                wait_pass_end(rec, t1 - 0.5, t1 + 5.0)
                prof.append(time.monotonic())
                jax.profiler.stop_trace()
            pushes = producer.finish(timeout=GRACE_S + 30)
            n_pushed = len(pushes["seq"])
            deadline = t1 + GRACE_S
            while (len(rec.deliveries) < n_pushed
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            t_end = time.monotonic()
        stats_after = clf.stats.snapshot()
        dev = jax.devices()[0]
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        facts = {
            "setup_s": {"before_run_cell": marks["start"] - t_begin,
                        **{k: marks[k] - marks[p] for p, k in
                           zip(list(marks), list(marks)[1:])}},
            "compiles_warmup": warm_compiles,
            "compile_s": meter.seconds,
            "compiles_in_window": at_t1[0] - at_t0[0],
            "path": clf.active_path,
            "pallas": clf.pallas_stats(),
            "wire": clf.wire_stats(),
            "resident": clf.resident_counters(),
            "ring": daemon.ingest_ring.counter_values(),
        }
    finally:
        if producer is not None:
            producer.stop()
        daemon.stop()
        # stop() signals the daemon's threads; wait for its loop to end
        for t in getattr(daemon, "_threads", ()):
            t.join(timeout=10.0)
    return SimpleNamespace(
        inputs=inputs, seconds=seconds, traced=traced,
        t_start=t_start, t0=t0, t1=t1, t_end=t_end, setup_s=setup_s,
        prof=prof, pushes=pushes, rec=rec, facts=facts,
        stats_delta=stats_after - stats_before, memory_peak_bytes=int(mem),
        gc_events=np.asarray(gcm.events).reshape(-1, 3),
        tracer_t0=at_t0[1], tracer_t1=at_t1[1],
        trace_dir=os.path.join(work, "trace") if traced else None,
    )


# -- after the window --------------------------------------------------------------


def per_record(r) -> SimpleNamespace:
    """Everything indexed by ring sequence number: pool index, packets,
    when the push committed, the producer's cumulative ring-blocked
    microseconds, delivery time (NaN: none) and the number of
    deliveries."""
    pu = r.pushes
    n_seq = int(pu["seq"].max()) + 1 if len(pu["seq"]) else 0
    pool = np.full(n_seq, -1, np.int64)
    pool[pu["seq"]] = pu["pool"]
    commit = np.full(n_seq, np.nan)
    commit[pu["seq"]] = pu["commit"]
    blocked = np.zeros(n_seq)
    blocked[pu["seq"]] = pu["blocked_us"]
    deliver = np.full(n_seq, np.nan)
    count = np.zeros(n_seq, np.int64)
    for seq, t, _res in r.rec.deliveries:
        if 0 <= seq < n_seq:
            count[seq] += 1
            if np.isnan(deliver[seq]):
                deliver[seq] = t
    packets = np.full(n_seq, r.inputs.record_packets, np.int64)
    return SimpleNamespace(pool=pool, commit=commit,
                           blocked_us=blocked, deliver=deliver, count=count,
                           packets=packets)


def check(r, recs) -> SimpleNamespace:
    """Compare every delivered record's verdicts and the statistics delta
    with the reference; count records never delivered, or delivered
    twice.  The reference runs here, after the window."""
    inp = r.inputs
    ref = reference.Reference(inp.table)
    results, target = ref.classify(inp.pool)
    n = inp.record_packets
    want = results.reshape(inp.pool_records, n)
    want_stats = np.zeros_like(r.stats_delta)
    rec_stats = [reference.rule_stats(want[i], inp.pool.pkt_len[i * n:(i + 1) * n])
                 for i in range(inp.pool_records)]
    mismatches = 0
    foreign = 0
    for seq, _t, res in r.rec.deliveries:
        if not 0 <= seq < len(recs.pool) or recs.pool[seq] < 0:
            foreign += 1
            continue
        p = int(recs.pool[seq])
        got = np.asarray(res, np.uint32)
        mismatches += (int(np.count_nonzero(got != want[p]))
                       if got.shape == want[p].shape else n)
        want_stats += rec_stats[p]
    # bytes the work reads, by the floor model of the configuration
    width = inp.table.rules.shape[1]
    wire_words = np.array([7 if (inp.pool.kind[i * n:(i + 1) * n] == 2).any()
                           or inp.pool.ip_words[i * n:(i + 1) * n, 1:].any()
                           else 4 for i in range(inp.pool_records)])
    hits = (target.reshape(inp.pool_records, n) >= 0).sum(axis=1)
    rec_bytes = n * (4 * wire_words + 4) + hits * 10 * width
    return SimpleNamespace(
        verdict_mismatches=int(mismatches),
        stats_mismatches=int(np.count_nonzero(r.stats_delta != want_stats)),
        undelivered_records=int(np.count_nonzero(recs.count == 0)),
        duplicate_deliveries=int(np.sum(np.maximum(recs.count - 1, 0))) + foreign,
        rec_bytes=rec_bytes,
    )


def attempted_failed(r, recs):
    """Packets of the records committed in the window, and those of them
    never served."""
    due = (recs.commit >= r.t0) & (recs.commit < r.t1)
    return (int(recs.packets[due].sum()),
            int(recs.packets[due & (recs.count == 0)].sum()))


def reduce_trace(r) -> dict | None:
    if not r.traced:
        return None
    path = trace.find_xplane(r.trace_dir)
    if path is None:
        raise RunError("the profiler wrote no trace")
    device, host = trace.read_events(path, [SINK_SPAN, PASS_SPAN])
    red = trace.reduce(device, host, IDLE_LABELS, IDLE_OTHER)
    if red is not None:
        red["window_s"] = r.prof[1] - r.prof[0]
    return red


def result_line(cell, r, seed: int) -> tuple:
    """(result dict, lines for stderr)."""
    import jax

    recs = per_record(r)
    chk = check(r, recs)
    tr = reduce_trace(r)
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    dev = jax.devices()[0]
    run = SimpleNamespace(
        **vars(r), recs=recs, check=chk, trace=tr,
        peak=peaks["devices"].get(dev.device_kind),
    )
    metrics = {}
    for m in cell_metrics(cell.bench, cell.name, r.traced):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = attempted_failed(r, recs)
    checks = {
        "verdict_mismatches": [chk.verdict_mismatches, 0],
        "stats_mismatches": [chk.stats_mismatches, 0],
        "undelivered_records": [chk.undelivered_records, 0],
        "duplicate_deliveries": [chk.duplicate_deliveries, 0],
    }
    correct = all(v <= lim for v, lim in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": r.memory_peak_bytes}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    info = [
        "facts " + json.dumps(r.facts, default=str),
        "run " + json.dumps({
            "seed": seed, "setup_s": r.setup_s, "records_pushed": len(recs.pool),
            "records_delivered": int(np.count_nonzero(recs.count)),
            "loop_passes": len(r.rec.passes),
        }),
    ]
    passes = np.asarray([(a, b, k) for a, b, k in r.rec.passes]).reshape(-1, 3)
    passes = passes[(passes[:, 1] > r.t0) & (passes[:, 0] < r.t1)]
    if len(passes) > 1:
        ms = (passes[:, 1] - passes[:, 0]) * 1e3
        info.append("loop " + json.dumps({
            "passes_in_window": int(len(passes)),
            "pass_ms_p50_p90_max": [float(np.percentile(ms, q)) for q in (50, 90, 100)],
            "between_passes_ms_max": float(np.max(passes[1:, 0] - passes[:-1, 1]) * 1e3),
            "packets_per_pass_p50": float(np.median(passes[:, 2])),
            "longest_passes_at_s_ms": [
                [float(passes[i, 0] - r.t0), float(ms[i])]
                for i in np.argsort(-ms)[:3]],
        }))
    g = r.gc_events[(r.gc_events[:, 1] > r.t0) & (r.gc_events[:, 0] < r.t1)]
    if len(g):
        gms = (g[:, 1] - g[:, 0]) * 1e3
        info.append("gc " + json.dumps({
            "collections_by_generation": np.bincount(
                g[:, 2].astype(int), minlength=3).tolist(),
            "total_ms": float(gms.sum()),
            "longest_at_s_ms_gen": [
                [float(g[i, 0] - r.t0), float(gms[i]), int(g[i, 2])]
                for i in np.argsort(-gms)[:3]],
        }))
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
        info.append("idle_by_host_span " + json.dumps(tr["idle_by_host_span"]))
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    info += [f"check {k}: {v} (limit {lim})" for k, (v, lim) in checks.items()]
    return out, info


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("action-only",), default=None,
                   help="plant the control: verdicts delivered as their "
                        "action byte alone (never used by the benchmark's runs)")
    args = p.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (RunError, OSError, KeyError, ValueError) as e:
        print(f"run: {e}", file=sys.stderr)
        return 2

    import jax

    if jax.default_backend() != "tpu":
        print(f"run: JAX came up on {jax.default_backend()!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < cell.chips:
        print(f"run: {cell.chips} chips needed, {len(jax.devices())} visible",
              file=sys.stderr)
        return 2
    from infw.platform import enable_compile_cache

    enable_compile_cache()
    alter = None
    if args.control == "action-only":
        alter = lambda _seq, res: np.asarray(res, np.uint32) & 0xFF  # noqa: E731
    work = tempfile.mkdtemp(prefix="infw-bench-")
    try:
        r = run_cell(cell, args.seed, args.seconds, bool(args.trace), work,
                     alter=alter)
        out, info = result_line(cell, r, args.seed)
    except RunError as e:
        print(f"run: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for line in info:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
