"""Codec + MAC: the self time of ``transport/*`` (step_wave,
frame_decode, mac_verify_batch, frame_encode, flush), share of the
traced window."""

from benchmarks.layer_metrics._spans import window_pct


def read(run):
    return window_pct(run, "self_s", "transport/")
