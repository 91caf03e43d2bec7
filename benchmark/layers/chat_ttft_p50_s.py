"""Client side, in the traced run (this process drives the engine, no router): median of first token minus the time due over the requests due in the traced window. Recorded, not judged: end to end it spreads wider than a bound may be (PERF.md, section 2)."""


def read(ctx):
    return ctx['judged'].get('ttft_p50_s')
