"""One serving-plane PROCESS of the fleet: plan-ship codec + the
``multiprocessing`` bootstrap target (docs/serving.md fleet section).

The router (``serving/fleet.py``) is a jax-clean module; everything
that must touch jax — decoding shipped weights onto the device,
exporting the plan, running today's full :class:`ReplicatedServer`
stack — lives here, and ONLY runs inside the spawned plane process.
Module level stays import-light (stdlib + numpy + the jax-free fault
harness) so the parent can reference :func:`plane_main` as a spawn
target without dragging jax into the router; the heavy imports happen
inside the functions, i.e. inside the child.

Plan shipping (the tentpole's integrity contract): a plan travels as

  - a pickled *skeleton* — the fitted pipeline (fused operators
    rebuild their composed closures inside ``__setstate__``, so the
    skeleton must unpickle standalone — weight slots cannot be
    stripped to sentinels);
  - the weights, AGAIN, as the zoo's bit-exact split-plane tensors
    (``uint16`` hi/lo planes + per-tensor CRC — the PR-13 encoding,
    unchanged). These are the AUTHORITATIVE bits: on arrival each is
    CRC-verified, decoded, required to be BIT-IDENTICAL to the
    skeleton's corresponding slot (a disagreement between the two
    channels means wire corruption or tampering), and then restored
    into the slots — the skeleton's own copies are never trusted
    un-cross-checked;
  - the export signature (item shape/dtype, max_batch, padding
    buckets) and the CLAIMED ``plan_fingerprint``.

After restore the plane re-exports the plan and recomputes the
fingerprint end-to-end (the ``fleet.rpc.send`` corrupt site models
wire corruption of a shipped weight plane). Any mismatch — CRC,
cross-channel bit-identity, or fingerprint — QUARANTINES the plane: it
stays up, answers heartbeats, and refuses every request with a named
error; wrong bits are never served (the zoo's posture, extended across
the process boundary).
"""

from __future__ import annotations

import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from keystone_tpu.utils import faults

from .fleet_rpc import RpcServer

__all__ = ["PlanShip", "encode_plan_ship", "decode_plan_ship",
           "plane_main"]

logger = logging.getLogger(__name__)


class PlanShip:
    """The cross-process form of one exported plan (see module
    docstring). ``tensors`` are zoo ``_PagedTensor`` objects — hi/lo
    ``uint16`` planes + per-tensor CRC."""

    __slots__ = ("skeleton", "tensors", "item_shape", "dtype",
                 "max_batch", "buckets", "fingerprint", "backend")

    def __init__(self, skeleton: bytes, tensors: List[Any],
                 item_shape: Tuple[int, ...], dtype: str,
                 max_batch: Optional[int], buckets: Sequence[int],
                 fingerprint: str, backend: str):
        self.skeleton = skeleton
        self.tensors = tensors
        self.item_shape = tuple(item_shape)
        self.dtype = str(dtype)
        self.max_batch = max_batch
        self.buckets = tuple(buckets)
        self.fingerprint = str(fingerprint)
        # The JAX backend of the process that exported the plan — what the
        # (jax-free) router requires every plane to come up on.
        self.backend = str(backend)

    def __getstate__(self):
        return {s: getattr(self, s) for s in self.__slots__}

    def __setstate__(self, state):
        for s in self.__slots__:
            setattr(self, s, state[s])


class ShipRejected(RuntimeError):
    """A shipped plan failed its integrity verification (tensor CRC or
    end-to-end fingerprint) — the receiving plane must quarantine."""


def encode_plan_ship(fitted, plan) -> PlanShip:
    """Encode ``fitted`` (the pipeline ``plan`` was exported from) for
    shipping. Runs in the jax-owning caller process (the process that
    fit the model), whose backend the ship records. The weight slots are walked in the zoo's sorted
    deterministic order and split-plane encoded (per-tensor CRC); the
    receiving plane re-walks the unpickled skeleton in the same order,
    so slot ``i`` on both sides names the same weight."""
    import jax

    from keystone_tpu.serving.zoo import (
        _collect_weight_slots,
        _encode_tensor,
    )

    graph = fitted.transformer_graph
    slots = _collect_weight_slots(graph)
    host = [np.asarray(a) for (_op, _k, _i, a) in slots]
    tensors = [_encode_tensor(a) for a in host]
    skeleton = pickle.dumps(fitted, protocol=4)
    return PlanShip(
        skeleton=skeleton,
        tensors=tensors,
        item_shape=plan.item_shape,
        dtype=str(plan.dtype),
        max_batch=plan.max_batch,
        buckets=plan.buckets,
        fingerprint=plan.fingerprint,
        backend=jax.default_backend(),
    )


def decode_plan_ship(ship: PlanShip):
    """Rebuild an :class:`ExportedPlan` from a ship, verifying every
    tensor CRC, the cross-channel bit-identity (split-plane tensors vs
    the skeleton's own slots) and the end-to-end ``plan_fingerprint``.
    Runs in the PLANE process (owns jax). Raises :class:`ShipRejected`
    on any integrity failure — callers quarantine, never serve."""
    import jax.numpy as jnp

    from keystone_tpu.data.durable import ShardCorrupted
    from keystone_tpu.serving.export import export_plan
    from keystone_tpu.serving.zoo import (
        _collect_weight_slots,
        _decode_tensor,
        _restore_slot,
    )

    try:
        decoded = [
            _decode_tensor(t, faults.SITE_FLEET_RPC_SEND)
            for t in ship.tensors
        ]
    except ShardCorrupted as e:
        raise ShipRejected(f"weight plane CRC mismatch: {e}") from e
    fitted = pickle.loads(ship.skeleton)
    slots = _collect_weight_slots(fitted.transformer_graph)
    if len(slots) != len(decoded):
        raise ShipRejected(
            f"skeleton carries {len(slots)} weight slots, ship carries "
            f"{len(decoded)} tensors"
        )
    for ordinal, ((op, k, i, skel_val), arr) in enumerate(
        zip(slots, decoded)
    ):
        skel = np.asarray(skel_val)
        if (skel.dtype != arr.dtype or skel.shape != arr.shape
                or skel.tobytes() != arr.tobytes()):
            raise ShipRejected(
                f"weight slot {ordinal} ({k}): split-plane channel "
                f"disagrees with skeleton channel — wire corruption "
                f"or tampering"
            )
        # The CRC'd split-plane copy is the authoritative one.
        _restore_slot(op, k, i, jnp.asarray(arr))
    example = np.zeros(ship.item_shape, np.dtype(ship.dtype))
    plan = export_plan(
        fitted, example, max_batch=ship.max_batch,
        buckets=list(ship.buckets),
    )
    if plan.fingerprint != ship.fingerprint:
        raise ShipRejected(
            f"fingerprint mismatch: shipped {ship.fingerprint}, "
            f"rebuilt {plan.fingerprint}"
        )
    return plan


# ---------------------------------------------------------------------------
# The plane process
# ---------------------------------------------------------------------------


def _plane_handler(state: Dict[str, Any]):
    """Build the RPC handler closure over the plane's mutable state."""
    from keystone_tpu.serving.batcher import (
        ServerClosed,
        ServerDegraded,
        ServerOverloaded,
    )

    def handler(req: Dict[str, Any]) -> Dict[str, Any]:
        op = req.get("op")
        if op == "ping":
            return {"ok": True, "pid": os.getpid(),
                    "quarantined": state["quarantined"] is not None}
        if op == "shutdown":
            state["shutdown"].set()
            return {"ok": True}
        if op == "stats":
            srv = state["server"]
            return {
                "ok": True,
                "quarantined": state["quarantined"],
                "fingerprint": state["fingerprint"],
                "stats": srv.stats() if srv is not None else {},
            }
        if op == "submit":
            if state["quarantined"] is not None:
                return {"ok": False, "error": "quarantined",
                        "message": state["quarantined"]}
            deadline_ms = req.get("deadline_ms")
            timeout_s = (deadline_ms / 1e3 + state["grace_s"]
                         if deadline_ms is not None
                         else state["default_timeout_s"])
            t0 = time.perf_counter()
            try:
                fut = state["server"].submit(
                    req["x"], deadline_ms=deadline_ms
                )
                y = fut.result(timeout=timeout_s)
            except ServerOverloaded as e:
                return {"ok": False, "error": "overloaded",
                        "message": str(e)}
            except (ServerDegraded, ServerClosed) as e:
                return {"ok": False, "error": "degraded",
                        "message": f"{type(e).__name__}: {e}"}
            state["hist"].observe(time.perf_counter() - t0)
            return {"ok": True, "y": np.asarray(y),
                    "fingerprint": getattr(fut, "plan_fingerprint",
                                           state["fingerprint"])}
        if op == "offer":
            # Lifecycle roll across the fleet: decode the candidate
            # ship (same CRC + fingerprint verification as boot) and
            # run it through THIS plane's LifecycleController —
            # validation gate, single-replica canary, zero-drop
            # promotion — exactly the PR-14 machinery, per process.
            if state["quarantined"] is not None:
                return {"ok": False, "error": "quarantined",
                        "message": state["quarantined"]}
            try:
                candidate = decode_plan_ship(req["ship"])
            except ShipRejected as e:
                return {"ok": False, "error": "ship_rejected",
                        "message": str(e)}
            ctrl = state["lifecycle"]()
            result = ctrl.offer(candidate)
            if result.get("published"):
                state["fingerprint"] = result["fingerprint"]
            return {"ok": True, "result": result}
        return {"ok": False, "error": "unknown_op",
                "message": f"unknown op {op!r}"}

    return handler


def plane_main(name: str, conn, ship: PlanShip,
               cfg: Dict[str, Any]) -> None:
    """Child-process entry: decode the shipped plan (quarantine on any
    integrity failure), stand up the full per-process serving stack
    (:class:`ReplicatedServer` + latency histogram + ``LiveExporter``
    publishing ``/snapshot.json``), serve the fleet RPC until told to
    shut down. ``conn`` is the bootstrap pipe: exactly one dict with
    the ports/pid/quarantine verdict is sent, then it is closed."""
    # Heavy imports here — this IS the jax-owning process.
    from keystone_tpu.obs.live import LiveExporter
    from keystone_tpu.obs.metrics import BucketedHistogram
    from keystone_tpu.serving.lifecycle import LifecycleController
    from keystone_tpu.serving.replicas import ReplicatedServer
    from keystone_tpu.utils.startup import (
        device_summary,
        enable_compile_cache,
    )

    enable_compile_cache()
    quarantined: Optional[str] = None
    plan = None
    # Which device this process actually got. A chip belongs to one
    # process: when the spawning parent holds it, backend start-up either
    # raises here or lands on the host CPU — the hello reports which, and
    # the router refuses a plane that is not on the ship's backend.
    device = {"backend": None, "device_kind": None, "device_count": 0}
    try:
        device = device_summary()
    except RuntimeError as e:
        quarantined = f"backend init failed: {e}"
        logger.warning(
            "fleet plane %s QUARANTINED (no backend): %s", name, e
        )
    if quarantined is None:
        try:
            plan = decode_plan_ship(ship)
        except ShipRejected as e:
            quarantined = str(e)
            logger.warning(
                "fleet plane %s QUARANTINED on arrival: %s", name, e
            )
        except Exception as e:  # noqa: BLE001 — quarantine, never serve
            quarantined = f"{type(e).__name__}: {e}"
            logger.warning(
                "fleet plane %s QUARANTINED (decode error): %r", name, e
            )

    server = None
    if quarantined is None:
        server = ReplicatedServer(
            plan,
            num_replicas=int(cfg.get("replicas", 2)),
            max_wait_ms=float(cfg.get("max_wait_ms", 2.0)),
            max_queue_depth=int(cfg.get("max_queue_depth", 1024)),
            restart_budget=int(cfg.get("replica_restart_budget", 3)),
            watchdog_interval_s=float(
                cfg.get("watchdog_interval_s", 0.02)
            ),
        )

    hist = BucketedHistogram()
    state: Dict[str, Any] = {
        "server": server,
        "hist": hist,
        "quarantined": quarantined,
        "fingerprint": ship.fingerprint,
        "shutdown": threading.Event(),
        "grace_s": float(cfg.get("deadline_grace_s", 5.0)),
        "default_timeout_s": float(cfg.get("default_timeout_s", 30.0)),
    }

    _lc_lock = threading.Lock()
    _lc: List[Any] = []

    def _lifecycle() -> LifecycleController:
        with _lc_lock:
            if not _lc:
                _lc.append(LifecycleController(
                    server, plan,
                    canary_sustain_s=float(
                        cfg.get("canary_sustain_s", 0.5)
                    ),
                    canary_min_samples=int(
                        cfg.get("canary_min_samples", 5)
                    ),
                ))
            return _lc[0]

    state["lifecycle"] = _lifecycle

    def _export_stats() -> Dict[str, Any]:
        doc: Dict[str, Any] = {
            "pid": os.getpid(),
            "name": name,
            **device,
            "quarantined": state["quarantined"],
            "fingerprint": state["fingerprint"],
            "latency_hist": hist.state_dict(),
        }
        srv = state["server"]
        if srv is not None:
            doc["server"] = srv.stats()
        return doc

    exporter = LiveExporter(
        {"fleet_plane": _export_stats},
        port=0,
        interval_s=float(cfg.get("metrics_interval_s", 0.25)),
    )
    rpc = RpcServer(_plane_handler(state))
    try:
        conn.send({
            "rpc_port": rpc.port,
            "metrics_port": exporter.port,
            "pid": os.getpid(),
            "quarantined": quarantined,
            "fingerprint": ship.fingerprint,
            **device,
        })
        conn.close()
        state["shutdown"].wait()
    finally:
        rpc.close()
        exporter.close()
        if server is not None:
            server.close()
