"""RBC: ``run_epoch``'s host walls of encode + verify + decode, mean
over the window's epochs."""


def read(run):
    stats = run.get("epoch_stats")
    if not stats:
        return None
    total = sum(
        s["rbc_encode_s"] + s["rbc_verify_s"] + s["rbc_decode_s"]
        for s in stats
    )
    return 1e3 * total / len(stats)
