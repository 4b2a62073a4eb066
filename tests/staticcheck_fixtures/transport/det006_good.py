"""Known-good DET006 fixture: the wave-signer discipline — a flush
buffers its whole egress wave and signs it in ONE sign_wire_wave call
(payload bodies encode once per distinct object through the shared
FrameEncodeMemo, MACs batch over the precomputed key schedules); the
boot path that runs before the peer pool exists carries a justified
pragma."""


def flush_outbound(auth, posts, memo, pool_ready):
    if pool_ready:
        items = [(msg, (receiver_id,)) for msg, receiver_id in posts]
        return [
            frames[rids[0]]
            for (_msg, rids), frames in zip(
                items, auth.sign_wire_wave(items, memo)
            )
        ]
    return [
        auth.sign_wire_many(msg, [rid])[rid]  # staticcheck: allow[DET006] pre-pool boot path
        for msg, rid in posts
    ]
