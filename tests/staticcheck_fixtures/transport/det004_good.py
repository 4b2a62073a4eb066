"""Known-good DET004 fixture: the wave-router seam discipline — the
transport buffers a delivery wave and hands it over in ONE serve_wave
call; the per-frame fallback for handlers without wave ingest carries
a justified pragma."""


def read_loop(inbound, handler, decode):
    batch = []
    for wire in inbound:
        batch.append(decode(wire))
    if not batch:
        return
    serve_wave = getattr(handler, "serve_wave", None)
    if serve_wave is not None:
        serve_wave(batch)
    else:
        for msg in batch:
            handler.serve_request(msg)  # staticcheck: allow[DET004] non-wave handler fallback
