"""The device's time by phase and its idle time by program span, from the
account the PROGRAM takes of the window's profile — shared by the five
readers that report it (``featurize_device_ms``, ``gram_device_ms``,
``solve_device_ms``, ``device_unscoped_pct``, ``idle_in_program_ms``).

The program's tracer follows the window's jax profile; when the profile is
over — at the first ``FittedPipeline.apply`` after it, which every driver
makes when it scores its probe rows — the session ends and keeps
``keystone_tpu.obs.device.device_account`` of that profile
(``obs.last_session().device_account``): a device plane apart, the SELF
time of every operation filed under the innermost ``ks.*`` name scope of its
path, and every idle gap split by the innermost ``ks.*`` host annotation
open over it. All values here are per fit and the MEAN over the device
planes, with the least and the most in the note. The account reads the same
events as the harness's ``op_seconds``: a note says so where the two totals
part by more than 0.5%. A program whose session holds no account (the parent
of the PR that added this, a rehearsal, a run with no profile) gives None.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

UNSCOPED, OUTSIDE = "unscoped", "outside"
AGREE_WITHIN = 0.005


def session_account() -> Optional[Dict[str, Any]]:
    """The device account of the program's last profile-following session."""
    from keystone_tpu import obs

    last_session = getattr(obs, "last_session", None)  # a program before PR 26 has none
    session = last_session() if last_session is not None else None
    return getattr(session, "device_account", None)  # nor, before PR 37, an account


def of_window(ctx) -> Optional[Dict[str, Any]]:
    """The account of the window ``ctx`` describes, looked up once a run."""
    if ctx.get("trace") is None or not ctx["window"]["fits"]:
        return None
    if "_device_account" not in ctx:
        found = ctx["_device_account"] = session_account()
        if found is None:
            ctx["notes"].append("device account: the program's session holds none")
        else:
            _cross_check(ctx, found)
    return ctx["_device_account"]


def _cross_check(ctx, found) -> None:
    planes = found["planes"]
    mine = sum(sum(p["by_scope_ns"].values()) for p in planes) / len(planes) / 1e9
    theirs = sum((ctx["trace"].get("op_seconds") or {}).values())
    clock = found.get("clock") or {}
    said = (f"device account: {len(planes)} plane(s), {mine:.4f} s of self time a plane "
            f"against the harness's {theirs:.4f}")
    if theirs and abs(mine - theirs) > AGREE_WITHIN * theirs:
        said += f" — the totals PART by {100 * (mine - theirs) / theirs:+.2f}%"
    if clock:
        said += (f"; after-the-fact spans laid by an offset whose spread over "
                 f"{clock['roots']} roots is {clock['spread_ns'] / 1e3:.1f} us")
    if "took_s" in found:
        said += f"; the program took {found['took_s']:.2f} s to read its profile"
    ctx["notes"].append(said)


def per_fit_ms(ctx, found, ns_of_plane) -> List[float]:
    """``ns_of_plane(plane)`` per fit in ms, a plane apart."""
    fits = ctx["window"]["fits"]
    return [ns_of_plane(p) / fits / 1e6 for p in found["planes"]]


def spread(values: List[float]) -> str:
    mean = sum(values) / len(values)
    if len(values) == 1:
        return f"{mean:.3f}"
    return f"{mean:.3f} (planes {min(values):.3f} – {max(values):.3f})"


def scopes_ms(ctx, metric: str, scopes: Iterable[str],
              beside: Iterable[str] = ()) -> Optional[float]:
    """Per fit, the device's self time under ``scopes`` (the mean over the
    planes); the note gives each scope apart, and the scopes ``beside`` them
    that are not in the sum. None where the account has none of ``scopes``:
    the cell does not run that phase."""
    found = of_window(ctx)
    if found is None:
        return None

    def apart(names):
        each = {s: per_fit_ms(ctx, found, lambda p, s=s: p["by_scope_ns"].get(s, 0.0))
                for s in names}
        return {s: v for s, v in each.items() if any(v)}

    summed = apart(scopes)
    if not summed:
        ctx["notes"].append(f"{metric}: the account holds none of {sorted(scopes)}")
        return None
    total = [sum(v[i] for v in summed.values()) for i in range(len(found["planes"]))]
    said = (f"{metric}: {spread(total)} ms a fit and device; by scope "
            + ", ".join(f"{s} {spread(v)}" for s, v in summed.items()))
    left_out = apart(beside)
    if left_out:
        said += "; beside it, not in the sum: " + ", ".join(
            f"{s} {spread(v)}" for s, v in left_out.items())
    ctx["notes"].append(said)
    return sum(total) / len(total)
