"""Host time per fit spent waiting for the device inside the program: the
``executor.drain`` spans, at both sites (``observe``: the executor's drains
around a node's first force; ``estimator_sync``: the barrier a traced fit
holds after the selected estimator's fit). A note gives each site's share."""

from benchmarks.layer_metrics import span_account


def read(ctx):
    found = span_account.of_window(ctx)
    if found is None:
        return None
    sites = {site: round(us / found["fits"] / 1e3, 3)
             for site, us in sorted(found["wait_sites_us"].items())}
    ctx["notes"].append(f"device_wait_ms by site, per fit: {sites}")
    return span_account.layer_ms(ctx, span_account.WAIT)
