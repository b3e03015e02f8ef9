"""The two phases of a block-streamed fit, from the program's spans: the
host's wait (``executor.drain`` ``site="block_epoch"``) after each of the
fit's two dispatches — epoch 1, which builds the per-block Gramian/factor
stash, and epochs 2+ in one more. Shared by ``block_first_epoch_ms`` and
``block_later_epoch_ms``. A program without such spans (the parent of the
PR that added this, a rehearsal, a run with no profile) gives None."""

from typing import Optional

from benchmarks.layer_metrics import span_account


def epoch_ms(ctx, first: bool) -> Optional[float]:
    """Milliseconds an epoch, per fit: of the first phase where ``first``,
    else of the later phase over the epochs it ran."""
    if span_account.of_window(ctx) is None:
        return None
    spans = span_account.session_spans() or []
    drains = [s for s in spans if s["name"] == "executor.drain"
              and s.get("args", {}).get("site") == "block_epoch"
              and (s["args"].get("epoch_from") == 1) == first]
    if not drains:
        return None
    epochs = sum(s["args"]["epoch_to"] - s["args"]["epoch_from"] + 1 for s in drains)
    waited_ms = sum(s["dur_us"] for s in drains) / 1e3
    if "_block_epochs_noted" not in ctx:
        ctx["_block_epochs_noted"] = True
        dispatches = [s for s in spans if s["name"] == "solver.block_epoch"]
        attrs = next((s["args"] for s in spans if s["name"] == "estimator.fit"
                      and "engine" in s.get("args", {})), {})
        fits = max(len(dispatches) // 2, 1)
        ctx["notes"].append(
            f"block epochs: estimator.fit attributes {attrs}; a solver.block_epoch span "
            f"{dispatches[0].get('args') if dispatches else None}; its dispatches took "
            f"{round(sum(s['dur_us'] for s in dispatches) / 1e3 / fits, 3)} ms a fit on the host")
    return waited_ms / epochs
