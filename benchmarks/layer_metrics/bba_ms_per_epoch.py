"""BBA + coin: ``run_epoch``'s own host wall of the phase (``bba_s``;
with dec_fused it holds the decryption wave too), mean over the
window's epochs.  A host wall of a phase that ends in host values, not
device time."""


def read(run):
    stats = run.get("epoch_stats")
    if not stats:
        return None
    return 1e3 * sum(s["bba_s"] for s in stats) / len(stats)
