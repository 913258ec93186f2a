"""The median round's time in the traced window, by the host's clock from
dispatch to the fetched metrics: what ``round_s`` reads without the host
stalls a one-chip machine takes now and then (PERF.md)."""
import statistics


def read(ctx):
    took = ctx["round_times"]
    return statistics.median(took) if took else None
