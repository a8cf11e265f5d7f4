"""Median, over the traced refreshes, of the refresh's wall time during which
no device operation ran: host grids, dispatch, validation, publish."""
import statistics


def read(ctx):
    spans = (ctx.trace or {}).get("span_host", {}).get("bench.refresh")
    if not spans:
        return None
    return 1e3 * statistics.median(host for _, host in spans)
