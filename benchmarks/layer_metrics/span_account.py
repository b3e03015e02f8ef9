"""The account of a fit's host path, from the program's own spans — shared
by the five readers that report it (``fit_retrace_ms``, ``retraces_per_fit``,
``device_wait_ms``, ``executor_self_ms``, ``solver_host_ms``).

While the window's jax profile runs, the program's tracer follows it
(``keystone_tpu.utils.profiling.follow_profiler``) and keeps its spans in
memory; ``keystone_tpu.obs.last_session()`` hands them over after the window.
A fit is one root ``pipeline.fit`` span with the root ``pipeline.build`` span
before it; everything under them hangs by ``parent_id``. Every instant of a
root belongs to exactly one span — the deepest one over it (self time: a
span's duration less what its children cover; ``jax.compile`` intervals that
nest are thereby united) — and every span to one of four layers, so the four
times add up to the roots' duration to the microsecond. All values are per
fit. A program without such a session (the parent of the PR that added this,
a rehearsal, a run with no profile) gives None, and the metric is left out.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from typing import Any, Dict, List, Optional

ROOTS = ("pipeline.build", "pipeline.fit")
RETRACE, WAIT, EXECUTOR, SOLVER = "retrace", "wait", "executor", "solver"
NEST_SLACK_US = 20  # clocks of a trace and of the trace it nests in may differ


def layer_of(name: str) -> str:
    if name == "jax.compile":
        return RETRACE
    if name == "executor.drain":
        return WAIT
    if name == "estimator.fit" or name.startswith(("solver.", "fold.")):
        return SOLVER
    # pipeline.build, pipeline.fit, fit.*, optimizer.rule.*, verify.pre_pass,
    # executor.node, cost.select — and whatever else a later PR spans
    return EXECUTOR


def session_spans() -> Optional[List[Dict[str, Any]]]:
    """The span records of the program's last profile-following session."""
    from keystone_tpu import obs

    last_session = getattr(obs, "last_session", None)  # a program before PR 26 has none
    session = last_session() if last_session is not None else None
    return None if session is None else session.spans()


def _owner(span: Optional[Dict[str, Any]]) -> str:
    if span is None:
        return "(no open span)"
    args = span.get("args", {})
    what = args.get("operator") or args.get("estimator")
    return f"{span['name']}[{what}]" if what else span["name"]


def account(spans: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Totals over every fit of the session, in whole microseconds."""
    by_id = {s["span_id"]: s for s in spans}
    kids: Dict[Any, List[Dict[str, Any]]] = defaultdict(list)
    for s in spans:
        kids[s["parent_id"]].append(s)
    roots = [s for s in kids[None] if s["name"] in ROOTS]
    fits = sum(s["name"] == "pipeline.fit" for s in roots)
    if not fits:
        return None
    layers: Counter = Counter()
    wait_sites: Counter = Counter()
    traces: Counter = Counter()  # outermost traces, by the span that caused them
    nested_traces = 0

    def paint(span, lo: int, hi: int) -> None:
        """Give [lo, hi) of ``span`` to its children where they cover it (the
        earlier sibling where two overlap) and the rest to ``span`` itself."""
        own, cursor = 0, lo
        for child in sorted(kids[span["span_id"]], key=lambda c: (c["ts_us"], -c["dur_us"])):
            a = max(child["ts_us"], cursor)
            b = min(child["ts_us"] + child["dur_us"], hi)
            if a >= b:
                continue
            own += a - cursor
            paint(child, a, b)
            cursor = b
        own += hi - cursor
        layer = layer_of(span["name"])
        layers[layer] += own
        if layer == WAIT:
            wait_sites[span.get("args", {}).get("site", "?")] += own

    def count_traces(span) -> None:
        nonlocal nested_traces
        found = [c for c in kids[span["span_id"]] if c["name"] == "jax.compile"
                 and c.get("args", {}).get("stage") == "trace"]
        for t in found:
            t0, t1 = t["ts_us"], t["ts_us"] + t["dur_us"]
            if any(o is not t and o["dur_us"] > t["dur_us"]
                   and o["ts_us"] - NEST_SLACK_US <= t0
                   and t1 <= o["ts_us"] + o["dur_us"] + NEST_SLACK_US for o in found):
                nested_traces += 1  # a jit traced inside another's trace
            else:
                traces[f"{_owner(by_id.get(t['parent_id']))} {t['args'].get('fun')}"] += 1
        for child in kids[span["span_id"]]:
            if child["name"] != "jax.compile":
                count_traces(child)

    spanned = Counter()
    for root in roots:
        paint(root, root["ts_us"], root["ts_us"] + root["dur_us"])
        count_traces(root)
        spanned[root["name"]] += root["dur_us"]
    return {"fits": fits, "layers_us": {k: layers[k] for k in (RETRACE, WAIT, EXECUTOR, SOLVER)},
            "spanned_us": dict(spanned), "wait_sites_us": dict(wait_sites),
            "traces": dict(traces), "nested_traces": nested_traces}


def of_window(ctx) -> Optional[Dict[str, Any]]:
    """The account of the window ``ctx`` describes, made once a run. Without
    a profile of the window there is no session that belongs to it."""
    if ctx.get("trace") is None:
        return None
    if "_span_account" not in ctx:
        spans = session_spans()
        ctx["_span_account"] = None if spans is None else account(spans)
        found = ctx["_span_account"]
        if found is not None and found["fits"] != ctx["window"]["fits"]:
            ctx["notes"].append(f"span account: {found['fits']} root pipeline.fit spans in the "
                                f"session, {ctx['window']['fits']} fits in the window")
    return ctx["_span_account"]


def layer_ms(ctx, layer: str) -> Optional[float]:
    """One layer's time per fit, in ms."""
    found = of_window(ctx)
    return None if found is None else found["layers_us"][layer] / found["fits"] / 1e3
