"""By hand, once (PR 26): one traced run of a fit cell with the profile kept,
and what the program's own spans say about it.

    python3 scripts/trace_gaps.py --workload timit_resident_fit_40k --seed 7 \
        --seconds 30 --out chiprun_out/gaps.json

Calls the benchmark's driver (``benchmarks.drivers.fit_loop.run``) as the
harness does, but reads the profile itself: the longest device gaps, each
with the chain of ``bench.*`` / ``ks.*`` host annotations over its midpoint
(the harness labels a gap by ``bench.`` spans alone), whether the ``ks.*``
spans nest in ``bench.fit`` on the profiler's clock, and the session's self
time by span name and retrace time by owner. Needs the chip; exits 2 without.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PREFIXES = ("bench.", "ks.")


def host_annotations(path):
    """{(plane, line): [(name, start_ns, duration_ns)]} of the annotations."""
    import jax

    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                     if e.name.startswith(PREFIXES)]
            if found:
                lines[(plane.name, line.name)] = found
    return lines


def tree(root, kids):
    """``root`` and every span under it."""
    out, stack = [], [root]
    while stack:
        out.append(stack.pop())
        stack += kids[out[-1]["span_id"]]
    return out


def self_us_by_name(roots, kids):
    """Self time by span name over the session's fits (children's cover out)."""
    out = collections.Counter()
    for s in (s for root in roots for s in tree(root, kids)):
        cover, cursor = 0, s["ts_us"]
        for c in sorted(kids[s["span_id"]], key=lambda c: c["ts_us"]):
            a, b = max(c["ts_us"], cursor), min(c["ts_us"] + c["dur_us"], s["ts_us"] + s["dur_us"])
            if b > a:
                cover, cursor = cover + b - a, b
        label = s["name"]
        what = s["args"].get("operator") or s["args"].get("estimator") or s["args"].get("site")
        if s["name"] == "jax.compile":
            label += f"[{s['args'].get('stage')}]"
        elif what:
            label += f"[{what}]"
        out[label] += s["dur_us"] - cover
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="timit_resident_fit_40k")
    parser.add_argument("--seed", type=int, default=2_147_483_777)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--out", default="chiprun_out/gaps.json")
    parser.add_argument("--rehearse", action="store_true",
                        help="toy sizes on whatever backend is there: control flow only")
    args = parser.parse_args()

    import jax

    if jax.devices()[0].platform == "cpu" and not args.rehearse:
        print("trace_gaps: needs the chip", file=sys.stderr)
        return 2
    from benchmarks import run, trace
    from benchmarks.drivers import fit_loop
    from benchmarks.layer_metrics import span_account
    from keystone_tpu import obs

    cell = run.load_cell(args.workload, rehearse=args.rehearse)
    if not args.rehearse:
        run.keep_compile_cache(jax)
    outcome = fit_loop.run(cell, seed=args.seed, seconds=args.seconds, trace=True,
                           devices=jax.devices()[:1])
    (path,) = glob.glob(os.path.join(outcome["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
    device_events, _ = trace.read_xplane(path)
    lines = host_annotations(path)
    everything = [e for events in lines.values() for e in events]
    fits = [e for e in everything if e[0] == trace.WINDOW_SPAN]
    window = (min(e[1] for e in fits), max(e[1] + e[2] for e in fits))
    # The ledger's ``jax.compile`` spans are recorded after the fact and have no
    # annotation: lay them on the profile's clock by the offset between a
    # ``pipeline.fit`` span and its ``ks.pipeline.fit`` annotation.
    session = obs.last_session()
    spans = session.spans() if session is not None else []
    fit_spans = sorted((s for s in spans if s["name"] == "pipeline.fit"), key=lambda s: s["ts_us"])
    fit_marks = sorted(e for e in everything if e[0] == "ks.pipeline.fit")
    if fit_spans and fit_marks:
        offset_ns = min(e[1] for e in fit_marks) - fit_spans[0]["ts_us"] * 1e3
        everything += [(f"ks.jax.compile[{s['args'].get('stage')}]",
                        s["ts_us"] * 1e3 + offset_ns, s["dur_us"] * 1e3)
                       for s in spans if s["name"] == "jax.compile"]
    ops = device_events[0] if device_events else []  # none on the CPU backend
    busy = trace.clip(trace.merge([(s, s + d) for _, s, d in ops]), *window)

    gaps, cursor = [], window[0]
    for start, end in busy + [(window[1], window[1])]:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    longest = []
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:5]:
        mid = (start + end) / 2
        over = sorted((e for e in everything if e[1] <= mid < e[1] + e[2]), key=lambda e: e[1])
        inside = collections.Counter()  # what the host did in the gap, by innermost span
        marks = sorted({start, end, *(t for e in everything for t in (e[1], e[1] + e[2])
                                      if start < t < end)})
        for a, b in zip(marks, marks[1:]):
            covering = [e for e in everything if e[1] <= a and b <= e[1] + e[2]]
            inside[max(covering, key=lambda e: e[1])[0] if covering else "unspanned"] += b - a
        longest.append({"gap_ms": (end - start) / 1e6,
                        "at_ms_of_window": (start - window[0]) / 1e6,
                        "chain_over_midpoint": [e[0] for e in over],
                        "innermost_ms": {k: round(v / 1e6, 3) for k, v in inside.most_common(8)}})

    nested = {"ks_events": sum(e[0].startswith("ks.") for e in everything), "lines": len(lines)}
    for key, events in lines.items():
        on_line = [e for e in events if e[0] == trace.WINDOW_SPAN]
        for name, s, d in events:
            if name == "ks.pipeline.fit":
                ok = any(f[1] <= s and s + d <= f[1] + f[2] for f in on_line)
                nested["fits_inside_bench_fit" if ok else "fits_outside"] = \
                    nested.get("fits_inside_bench_fit" if ok else "fits_outside", 0) + 1

    found = span_account.account(spans) if spans else None
    fits_n = found["fits"] if found else 1
    by_id = {s["span_id"]: s for s in spans}
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s["parent_id"]].append(s)

    # one row a fit, in order: [build + fit ms, retrace, wait, executor, solver]
    roots = sorted((s for s in kids[None] if s["name"] in span_account.ROOTS),
                   key=lambda s: s["ts_us"])
    per_fit, pending = [], []
    for root in roots:
        pending += tree(root, kids)
        if root["name"] == "pipeline.fit":
            one = span_account.account(pending)
            per_fit.append([round(sum(one["spanned_us"].values()) / 1e3, 1)]
                           + [round(us / 1e3, 1) for us in one["layers_us"].values()])
            pending = []
    compiles = collections.Counter()  # ms per fit by owner, program and stage
    for root in roots:
        for s in tree(root, kids):
            if s["name"] == "jax.compile":
                owner = by_id.get(s["parent_id"], {})
                what = owner.get("args", {}).get("operator", "")
                compiles[f"{owner.get('name')}[{what}] {s['args'].get('fun')} "
                         f"{s['args'].get('stage')}"] += s["dur_us"]
    result = {
        "workload": args.workload, "seed": args.seed, "correct": outcome["correct"],
        "window": outcome["window"], "busy_s": sum(b - a for a, b in busy) / 1e9,
        "traced_window_s": (window[1] - window[0]) / 1e9,
        "longest_gaps": longest, "nesting": nested, "account": found,
        "self_ms_per_fit_by_span": {k: round(v / fits_n / 1e3, 3) for k, v in
                                    self_us_by_name(roots, kids).most_common(40)},
        "per_fit_ms_spanned_retrace_wait_executor_solver": per_fit,
        "compile_ms_per_fit_nested_counted_twice": {
            k: round(v / fits_n / 1e3, 3) for k, v in compiles.most_common(24)},
        "spans_per_fit": round(len(spans) / fits_n, 1), "notes": outcome["notes"],
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
