"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It exits 2, with nothing on stdout, unless JAX finds a
TPU with at least the cell's number of chips.  Set-up (imports, native
build, keys, traffic from the seed, warm-up of the cell's own shapes)
is timed as ``setup_s``; then the window; then the drain, the peak
memory, the counters, and last the comparison with the plain reference
(benchmarks/reference.py) that decides ``correct``.  Set-up's parts go
to stdout on lines that start ``[bench]``; the last line of stdout is
the one JSON object of the result.  The numbers compared, each beside
its limit, are the last lines of stderr and the last key of the result.
A configuration with a write-ahead log keeps its logs in a directory of
this run's own under ``.bench_wal/`` in the checkout; they are read
back as part of the comparison and removed on every way out.  A cell
whose traffic file holds a fault schedule has validators killed and
restarted from those logs inside the window (benchmarks/executors.py),
and says what the schedule did on ``[bench] fault`` lines.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics: the profiler runs over the window's last whole loop
iterations (rounds, epochs), from the first boundary within TRACE_SECONDS
(2.5), or within the longest iteration timed so far where that is more, of
the window's nominal end; in a cell with a fault schedule, of its last
event, so that the restart, the round that catches up, what the clients
send after it and the drain are what is traced (an open loop with no
boundary from there to its end starts the trace as the window closes,
over the drain); the counters cover the whole of the window.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up starts here, before the imports

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.latency import latencies_ms, percentile  # noqa: E402

TRACE_SECONDS = 2.5
TRACE_DIR = ROOT / ".bench_trace"
SPAN_NAMES = (
    "traced_window", "probe", "start_epoch", "step", "idle_phase",
    "submit", "wait_arrival", "run_epoch", "drain",
)
EXIT_NO_CHIP = 2


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def require_chip(chips: int) -> Dict:
    """The device as JAX reports it; raises NoChip anywhere but on a
    TPU with ``chips`` devices.  Tests patch this function."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < chips:
        raise NoChip(
            f"this cell needs {chips} TPU chip(s); JAX found platform "
            f"{platform!r}, device_kind {devices[0].device_kind!r}, "
            f"{len(devices)} device(s)"
        )
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def trace_start_s(seconds: float, longest_round_s: float) -> float:
    """From when on, in seconds of the window, a loop boundary starts
    the trace: TRACE_SECONDS before the nominal end, or one longest
    round before it where rounds are longer, so that some boundary
    falls in the stretch and what is traced is whole rounds."""
    return max(0.0, seconds - max(TRACE_SECONDS, longest_round_s))


def trace_anchor_s(seconds: float, faults) -> float:
    """The moment of the window (``seconds`` long) that a traced run
    has to see, which ``trace_start_s`` then starts before: the nominal
    end; under a fault schedule (``executors.FaultSchedule``) its last
    event, which is what the cell exists for and falls seconds before
    the end."""
    if faults is None or not faults.events:
        return seconds
    return faults.events[-1].at * seconds


class Tracer:
    """The profiler over the window's last whole loop iterations.
    ``tick`` is called at every loop boundary; the trace starts at the
    first one at or after ``trace_start_s`` of ``seconds`` (the window's
    length, or ``trace_anchor_s``) and ends with the window.  The
    longest iteration is what warm-up timed (``warm_round_s``) and what
    the ticks of this window lie apart."""

    def __init__(self, on: bool, seconds: float, spans, name: str,
                 counters: Callable[[], Dict],
                 warm_round_s: float = 0.0) -> None:
        self.on = on
        self._counters = counters
        self.counters: Dict = {}  # at the trace's two ends
        self._seconds = seconds
        self._longest = warm_round_s
        self._last_tick: Optional[float] = None
        self._spans = spans
        self._dir = TRACE_DIR / name
        self._window = None
        self.started = False

    def _probe(self) -> None:
        from benchmarks.meters import probe_round_trip

        with self._spans("probe"):
            probe_round_trip()

    def tick(self, now: float) -> None:
        if not self.on:
            return
        if self._last_tick is not None:
            self._longest = max(self._longest, now - self._last_tick)
        self._last_tick = now
        if self.started:
            # the device's tracer may start a moment after the host's:
            # one probe at every loop boundary, 1.4 ms each
            self._probe()
            return
        if now >= trace_start_s(self._seconds, self._longest):
            self._start()

    def _start(self) -> None:
        import jax.profiler

        shutil.rmtree(self._dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(self._dir), profiler_options=options)
        self.started = True
        self.counters["before"] = self._counters()
        self._spans.on = True
        self._window = jax.profiler.TraceAnnotation("traced_window")
        self._window.__enter__()
        self._probe()

    def stop(self) -> None:
        """Ends the traced part; ``finish`` reads it.  The held-backlog
        loop calls this as its window closes, so that the drain's
        rounds, which no end-to-end metric covers, are not traced."""
        if not self.started or "after" in self.counters:
            return
        import jax.profiler

        t0 = time.perf_counter()
        self._probe()
        self.counters["after"] = self._counters()
        self._window.__exit__(None, None, None)
        self._spans.on = False
        jax.profiler.stop_trace()
        self._stop_s = time.perf_counter() - t0

    def finish(self) -> Optional[Dict]:
        if not self.started:
            return None
        from benchmarks import trace_reduce

        self.stop()
        t0 = time.perf_counter()
        try:
            trace = trace_reduce.load_xplane(str(self._dir), SPAN_NAMES)
            reduced = trace_reduce.reduce(trace)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        if reduced["dropped"]:
            say("the device dropped trace buffers: busy_s reads low")
        before, after = self.counters["before"], self.counters["after"]
        unit = "rounds" if "rounds" in after else "epochs"
        say(f"traced {reduced['window_s']:.3f} s from a loop boundary: "
            f"{after[unit] - before[unit]} whole {unit}; stopping the "
            f"profiler took {self._stop_s:.2f} s, reading the trace "
            f"{time.perf_counter() - t0:.2f} s")
        return reduced


# -- end-to-end metrics: taken by the harness, over the whole window --------


def _latency_pctl(run: Dict, stamps: str, q: float) -> float:
    """Percentile of due time -> the stamp of the epoch that settled
    the transaction, over EVERY transaction due in the window."""
    cache = run.setdefault("_latencies_ms", {})
    if stamps not in cache:
        cache[stamps] = latencies_ms(run, stamps)
    return percentile(cache[stamps], q)


def _settled_tx_per_s(run: Dict) -> float:
    return run["settled_in_window"] / (run["t_end"] - run["t0"])


END_TO_END: Dict[str, Callable[[Dict], float]] = {
    "settled_tx_per_s": _settled_tx_per_s,
    "settle_p50_ms": lambda r: _latency_pctl(r, "t_settled", 0.50),
    "settle_p90_ms": lambda r: _latency_pctl(r, "t_settled", 0.90),
    "settle_p99_ms": lambda r: _latency_pctl(r, "t_settled", 0.99),
    "order_p50_ms": lambda r: _latency_pctl(r, "t_ordered", 0.50),
    "setup_s": lambda r: r["setup_s"],
}


def _say_faults(faults: Dict, t0: float, t_settled: List[float],
                rounds: List[tuple]) -> None:
    """The fault schedule as it was applied, on ``[bench]`` lines."""
    for ev in faults["events"]:
        near = range(max(0, ev["round"] - 2), min(len(rounds), ev["round"] + 5))
        say(f"fault: rounds {near[0]}..{near[-1]} around the {ev['kind']}, "
            "seconds (delivery waves): " + ", ".join(
                f"{rounds[k][1] - rounds[k][0]:.2f} ({faults['round_waves'][k]})"
                for k in near
            ))
    for ev in faults["events"]:
        nodes = ev["nodes"]
        line = (
            f"fault: {ev['kind']} {nodes[0]}..{nodes[-1]} ({len(nodes)}) at "
            f"{ev['t'] - t0:.3f} s (due {ev['due_s']:.3f}), "
            + (f"in round {ev['round']} after delivery wave {ev['wave']} of its "
               f"{faults['round_waves'][ev['round']]}" if ev["wave"] else
               f"between rounds, before round {ev['round']}")
            + f", {ev['epochs_ordered']} epochs "
            f"stamped ordered and {ev['epochs_settled']} settled (warm-up's among "
            f"them); took {ev['took_s'] * 1e3:.1f} ms"
        )
        if ev["kind"] == "kill":
            line += f", {ev['resubmitted']} transactions resubmitted"
        say(line)
    for o in faults["outages"]:
        if "t_restart" not in o:
            continue
        nid = o["node"]
        stamped = sum(1 for t in t_settled if o["t_kill"] < t <= o["t_restart"])
        back = (
            f"in service {o['t_in_service'] - o['t_restart']:.3f} s later at "
            f"epoch {o['settled_in_service']}, in round {o['round_in_service']}"
            if "t_in_service" in o else "never back in service"
        )
        say(f"fault: {nid} killed at epoch {o['settled_at_kill']}, {stamped} "
            f"epochs stamped settled by the others while it was down, replayed "
            f"{o['settled_at_restart']} epochs from its log in "
            f"{o['replay_s'] * 1e3:.1f} ms, {back}")


# -- one run ------------------------------------------------------------------


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    root: pathlib.Path = ROOT,
    fault: Optional[Callable] = None,
    t_process: Optional[float] = None,
) -> Dict:
    """Set-up, window, drain, comparison; returns the result object.
    ``fault`` (tests and benchmarks/control.py only) is called with the
    warmed-up executor and breaks the timed path under it.  Whichever
    way a run ends, its executor is closed: a logged configuration's
    logs are hundreds of megabytes that a checkout must not keep."""
    t_process = time.perf_counter() if t_process is None else t_process
    with contextlib.ExitStack() as on_exit:
        return _run_cell(
            workload, seed, seconds, trace, root, fault, t_process, on_exit
        )


def _run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    root: pathlib.Path,
    fault: Optional[Callable],
    t_process: float,
    on_exit: contextlib.ExitStack,
) -> Dict:
    from benchmarks import spec

    cell = spec.load_cell(workload, root)
    device = require_chip(cell.chips)
    import jax  # noqa: F401  (require_chip imported it; the gate comes first)

    from benchmarks import reference
    from benchmarks.executors import EXECUTORS, Spans
    from benchmarks.meters import CompileMeter, dispatch_round_trip_us
    from benchmarks.traffic import open_loop_schedule
    from cleisthenes_tpu.native import build
    from cleisthenes_tpu.ops import placement
    from cleisthenes_tpu.utils.compile_cache import enable_compile_cache

    # every program goes to the persistent cache, the small ones too, so
    # that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    say(f"platform {device['platform']}, device_kind {device['kind']}, "
        f"{device['count']} device(s); compile cache {cache_dir}")
    t_b = time.perf_counter()
    for name, load in (
        ("gf256", build.load_gf256),
        ("modpow256", build.load_modpow),
        ("sha256rows", build.load_sha256),
    ):
        if load() is None:
            raise RuntimeError(f"native kernel {name} did not build or load")
    t_c = time.perf_counter()
    spans = Spans()
    placement.reset()
    executor = EXECUTORS[cell.config["executor"]](cell, seed, spans, meter)
    on_exit.callback(executor.close)
    t_d = time.perf_counter()
    loop = cell.traffic["loop"]
    schedule = None
    if loop == "open":
        due, arrivals = open_loop_schedule(
            cell.traffic, cell.config["tx_bytes"], seed, seconds
        )
        schedule = (due.tolist(), arrivals)
    t_e = time.perf_counter()
    executor.warm_up()
    round_trip = dispatch_round_trip_us()
    t_f = time.perf_counter()
    if fault is not None:
        fault(executor)
    gc.collect()
    gc.freeze()  # set-up's garbage is not collected inside the window
    tracer = Tracer(
        trace, trace_anchor_s(seconds, getattr(executor, "faults", None)),
        spans, workload, executor.counters, executor.clock.longest_s,
    )
    before = executor.counters()
    compile_before = (meter.seconds, meter.cache_hits)
    setup_s = time.perf_counter() - t_process
    say(
        f"set-up {setup_s:.2f} s: imports and gate {t_b - t_process:.2f}, "
        f"native build {t_c - t_b:.2f}, keys and cluster {t_d - t_c:.2f}, "
        f"traffic {t_e - t_d:.2f}, warm-up {t_f - t_e:.2f} "
        f"({meter.count} compilations in {meter.seconds:.2f} s, "
        f"{meter.cache_hits} persistent-cache hits), "
        f"round trip p50 {round_trip:.1f} us"
    )

    # ---- the window ----
    if loop == "open":
        ends = executor.run_open(schedule[0], schedule[1], seconds, tracer.tick)
    elif loop == "backlog":
        ends = executor.run_backlog(seconds, tracer.tick, tracer.stop)
    elif loop == "epoch":
        ends = executor.run_epochs(seconds, tracer.tick)
    else:
        raise spec.SpecError(f"traffic loop {loop!r} is not one the harness has")
    reduced = tracer.finish()
    peak = memory_peak_bytes(cell.chips)
    after = executor.counters()
    compile_s, cache_hits = (meter.seconds - compile_before[0],
                             meter.cache_hits - compile_before[1])

    # ---- what the window did, as plain data ----
    t0, t_end = ends["t0"], ends["t_end"]
    run: Dict = {
        "cell": workload,
        "config": cell.config,
        "executor": executor.kind,
        "loop": loop,
        "seconds": seconds,
        "t0": t0,
        "t_end": t_end,
        "setup_s": setup_s,
        "counters": {"before": before, "after": after,
                     "trace": tracer.counters},
        "trace": reduced,
        "device_kind": device["kind"],
    }
    if executor.kind == "served":
        obs = executor.observe()
        # the logs are read while they are there; close() removes them
        durable = {}
        if obs["wal"] is not None:
            held = sum(log["held_bytes"] for log in obs["wal"]["logs"].values())
            say(f"write-ahead logs: {after['wal_bytes']} bytes written, {held} "
                f"held after the last {obs['wal']['durable_after']}, "
                f"{obs['wal']['syncs']} syncs")
            durable = reference.compare_wal(obs)
        executor.close()
        faults = executor.fault_report()
        settled_in = reference.settled_epochs(obs)
        t_settled = executor.t_settled
        run.update(
            settled_in=settled_in,
            t_settled=t_settled,
            t_ordered=executor.t_ordered,
            timed=executor.timed,
            timed_ok=executor.timed_ok,
            due=executor.due,
            late_s=executor.late,
            submit_s=executor.submit_s,
            rounds=executor.round_log,
        )
        if faults is not None:
            run["faults"] = faults
            _say_faults(faults, t0, t_settled, executor.round_log)
        ledger = obs["ledgers"][reference.witness(obs)]
        in_window = [
            e for e, at in enumerate(t_settled) if t0 < at <= t_end
        ]
        run["epochs_in_window"] = len(in_window)
        run["rounds_in_window"] = ends.get("rounds")
        run["settled_in_window"] = sum(
            len(txs) for e in in_window for txs in ledger[e].values()
        )
        attempted = len(executor.timed)
        failed = sum(
            1
            for tx, ok in zip(executor.timed, executor.timed_ok)
            if not ok or tx not in settled_in
        ) + len((faults or {}).get("never_back", ()))
        numbers = dict(reference.compare_served(obs), **durable)
    else:
        first = ends["first_epoch"]
        obs = executor.observe(first)
        rows = executor.epochs[first:]
        executor.close()
        run["epochs_in_window"] = len(rows)
        run["epoch_stats"] = [row["stats"] for row in rows]
        committed = set()
        for row in obs["epochs"]:
            for txs in (row["committed"] or {}).values():
                committed.update(txs)
        submitted = [
            tx for row in obs["epochs"]
            for txs in row["submitted"].values() for tx in txs
        ]
        attempted = len(submitted)
        failed = sum(1 for tx in submitted if tx not in committed)
        run["settled_in_window"] = attempted - failed
        numbers = reference.compare_lockstep(obs)
    correct = reference.verdict(numbers)

    # ---- the result ----
    metrics: Dict[str, Dict] = {}
    if trace:
        for m in cell.per_layer:
            value = spec.load_reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = END_TO_END[m["name"]](run)
            if math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            else:
                say(f"{m['name']} is beyond the sample: more than that share "
                    f"of the window's transactions failed")
    device = dict(device, memory_peak_bytes=peak)
    result: Dict = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    late = sorted(run.get("late_s") or ())
    compiled = meter.names[before["compiles"]:after["compiles"]]
    say(f"window {t_end - t0:.3f} s, {run['epochs_in_window']} epochs, "
        f"{run['settled_in_window']} transactions settled; "
        f"{len(compiled)} compilations in the window"
        + (f" ({', '.join(compiled)}; {compile_s:.2f} s, {cache_hits} "
           f"persistent-cache hits)" if compiled else "")
        + (f"; generator late p95 {percentile(late, 0.95) * 1e3:.1f} ms"
           if late and loop == "open" else ""))
    result["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in numbers.items()
    }
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a run that is told to end leaves as one that raises does
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(128 + signal.SIGTERM))
    try:
        result = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_process=_T_PROCESS,
        )
    except NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return EXIT_NO_CHIP
    except ImportError as exc:
        print(f"[bench] the program is not in this checkout: {exc}",
              file=sys.stderr)
        return EXIT_NO_CHIP
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"[bench] compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
