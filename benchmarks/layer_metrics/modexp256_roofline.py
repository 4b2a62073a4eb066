"""ops/ kernels: the 256-bit exponentiation programs' share of the
chip's roofline over the traced part of the window.  Device time is
the summed "XLA Modules" events of the three jitted programs; the work
is reckoned from the placement tally's device items over the same
part (benchmarks/work.py), against the int8 peak (benchmarks/peaks.py).
Returns nothing where no such program ran."""

from benchmarks.layer_metrics._delta import placement_delta
from benchmarks.peaks import peaks_for
from benchmarks.work import modexp256_work, roofline_pct

PROGRAMS = ("jit__pow_fused", "jit__dual_pow_fused", "jit__pow_fused_grouped")


def read(run):
    trace = run.get("trace")
    ends = run["counters"].get("trace") or {}
    if not trace or "before" not in ends or "after" not in ends:
        return None
    seconds = sum(trace["programs"].get(p, 0.0) for p in PROGRAMS)
    work = modexp256_work(placement_delta(
        ends["before"]["placement"], ends["after"]["placement"]
    ))
    if seconds <= 0.0 or work["exps"] == 0:
        return None
    return roofline_pct(work, seconds, peaks_for(run["device_kind"]))
