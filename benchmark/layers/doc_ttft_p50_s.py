"""Client side: median of first token minus submit over the requests that completed, closed loop (recorded, not judged)."""


def read(ctx):
    return ctx['judged'].get('doc_ttft_p50_s')
