"""Serving-fleet chaos suite (ISSUE 20 acceptance): SIGKILL a plane
process mid-Poisson-storm and the fleet books still balance EXACTLY
(``offered == completed + rejected + failed`` at the router — the
zero-drop contract at PROCESS scope), the watchdog respawns the dead
plane through the ``fleet.plane.spawn`` fault site within its restart
budget, and the merged fleet p99 stays computable through the degraded
window (the dead plane's last-scraped histogram stays in the merge).
Spawn-fault exhaustion ("fleet.plane.spawn" error rules burning the
budget) evicts the plane LOUDLY with the surviving fleet intact; a
fingerprint-corrupted plan ship QUARANTINES the receiving plane (the
"fleet.rpc.send" corrupt site models wire corruption of a shipped
weight plane, caught by the split-plane CRCs).

The Poisson storm legs are marked ``slow`` so the tier-1 wall is
unchanged; run the full suite with ``bin/fleet-chaos`` (or
``pytest -m chaos``).
"""

import copy
import json
import os
import signal
import time

import numpy as np
import pytest

from keystone_tpu.serving.export import export_plan
from keystone_tpu.serving.fleet import (
    FleetBackendMismatch,
    FleetPlaneDied,
    FleetRouter,
    FleetSaturated,
)
from keystone_tpu.serving.fleet_plane import (
    ShipRejected,
    decode_plan_ship,
    encode_plan_ship,
)
from keystone_tpu.serving.loadgen import run_multi_tenant_open_loop
from keystone_tpu.utils.faults import FaultPlan, FaultRule

from tests._serving_util import TINY_D_IN, fit_tiny_mnist

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def shipment():
    """One fitted pipeline + its encoded plan ship, shared across the
    module (the fit dominates setup cost)."""
    fitted, X = fit_tiny_mnist()
    plan = export_plan(
        fitted, np.zeros(TINY_D_IN, np.float32), max_batch=8
    )
    return fitted, plan, X, encode_plan_ship(fitted, plan)


def _fleet(ship, num_planes=2, **kw):
    kw.setdefault("replicas_per_plane", 1)
    kw.setdefault("heartbeat_interval_s", 0.1)
    kw.setdefault("heartbeat_timeout_s", 3.0)
    kw.setdefault("restart_budget", 2)
    kw.setdefault("spawn_retry_delay_s", 0.01)
    return FleetRouter(ship, num_planes=num_planes, **kw)


def _books_balance(stats):
    return stats["aggregate_offered"] == (
        stats["completed"] + stats["rejected"] + stats["failed"]
    )


class TestShipIntegrity:
    def test_round_trip_reproduces_fingerprint(self, shipment):
        _fitted, plan, _X, ship = shipment
        rebuilt = decode_plan_ship(copy.deepcopy(ship))
        assert rebuilt.fingerprint == plan.fingerprint

    def test_tampered_weight_plane_rejected(self, shipment):
        """Flip one bit in a shipped split-plane tensor: the per-tensor
        CRC must reject the ship — wrong bits never become a plan."""
        _fitted, _plan, _X, ship = shipment
        bad = copy.deepcopy(ship)
        t = bad.tensors[0]
        plane = t.raw if t.raw is not None else t.hi
        plane.flat[0] ^= 1
        with pytest.raises(ShipRejected, match="CRC"):
            decode_plan_ship(bad)

    def test_wire_corruption_rule_rejected(self, shipment):
        """The chaos-plan form of the same contract: a corrupt rule at
        "fleet.rpc.send" flips bytes inside the decode path and the
        CRC catches it."""
        _fitted, _plan, _X, ship = shipment
        plan = FaultPlan([
            FaultRule("fleet.rpc.send", "corrupt", p=1.0),
        ])
        with plan:
            with pytest.raises(ShipRejected, match="CRC"):
                decode_plan_ship(copy.deepcopy(ship))

    def test_claimed_fingerprint_mismatch_rejected(self, shipment):
        _fitted, _plan, _X, ship = shipment
        bad = copy.deepcopy(ship)
        bad.fingerprint = "0" * len(bad.fingerprint)
        with pytest.raises(ShipRejected, match="fingerprint"):
            decode_plan_ship(bad)


class TestBackendOwnership:
    def test_plane_on_another_backend_fails_boot_by_name(self, shipment):
        """One process owns one chip: a plane whose hello reports another
        backend than the process that exported the plan (here: a ship
        claiming a TPU export, planes coming up on the CPU — what a
        one-chip host gives planes spawned beside the chip's owner)
        makes the router raise at boot, by name, leaving no plane
        behind. It never quietly serves from somewhere else."""
        import multiprocessing

        _fitted, _plan, _X, ship = shipment
        assert ship.backend == "cpu"
        elsewhere = copy.deepcopy(ship)
        elsewhere.backend = "tpu"
        with pytest.raises(FleetBackendMismatch,
                           match="one process owns one chip") as err:
            _fleet(elsewhere, num_planes=2)
        assert "'cpu'" in str(err.value) and "'tpu'" in str(err.value)
        deadline = time.monotonic() + 10.0
        while multiprocessing.active_children() \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert multiprocessing.active_children() == []


class TestFleetKill:
    def test_sigkill_respawn_books_balance(self, shipment):
        """The tier-1 core of the tentpole: SIGKILL one plane under
        traffic — its in-flight requests fail with the NAMED
        FleetPlaneDied, the watchdog respawns it (new pid), the books
        balance exactly across the kill, and the merged fleet
        histogram keeps the dead plane's observations."""
        _fitted, _plan, X, ship = shipment
        fleet = _fleet(ship, num_planes=2)
        try:
            for i in range(20):
                fleet.submit(X[i % len(X)]).result(timeout=30)
            time.sleep(0.3)  # let the watchdog scrape the histograms
            pre_count = fleet.stats()["fleet_latency_count"]
            assert pre_count >= 20

            victim = fleet.plane_pids()["plane0"]
            os.kill(victim, signal.SIGKILL)
            named = 0
            for i in range(40):
                try:
                    fleet.submit(X[i % len(X)]).result(timeout=30)
                except FleetPlaneDied:
                    named += 1
                time.sleep(0.01)

            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                s = fleet.stats()
                if s["restarts_total"] >= 1 and s["healthy_planes"] == 2:
                    break
                time.sleep(0.1)
            s = fleet.stats()
            assert s["restarts_total"] >= 1
            assert s["healthy_planes"] == 2
            assert s["evicted_planes"] == []
            assert fleet.plane_pids()["plane0"] != victim
            # Every plane's block says where it runs.
            assert {p["backend"] for p in s["planes"].values()} == {"cpu"}
            # Books: exact, with every kill-window failure NAMED.
            assert _books_balance(s), s
            assert s["failed"] == named
            # The dead plane's scraped observations survive the kill in
            # the fleet merge.
            assert s["fleet_latency_count"] >= pre_count
            assert s["fleet_p99_latency_s"] is not None
            # Post-respawn the fleet serves normally.
            fleet.submit(X[0]).result(timeout=30)
        finally:
            fleet.close()
        assert fleet.accounting_ok()

    @pytest.mark.slow
    def test_sigkill_mid_poisson_storm(self, shipment):
        """The full acceptance storm: 8 tenants of open-loop Poisson
        arrivals against a 4-plane fleet; one plane SIGKILLed
        mid-storm. The loadgen's books and the router's books must
        BOTH balance, the watchdog must respawn, and the merged p99
        must stay computable through the degraded window."""
        _fitted, _plan, X, ship = shipment
        fleet = _fleet(ship, num_planes=4, replicas_per_plane=1,
                       heartbeat_interval_s=0.05)
        killed = {}
        try:
            def submit(tenant, x, deadline_ms=None):
                return fleet.submit_tenant(tenant, x,
                                           deadline_ms=deadline_ms)

            import threading

            def killer():
                time.sleep(1.2)
                killed["pid"] = fleet.plane_pids()["plane1"]
                os.kill(killed["pid"], signal.SIGKILL)

            kt = threading.Thread(target=killer)
            kt.start()
            report = run_multi_tenant_open_loop(
                submit,
                lambda tenant, i: X[i % len(X)],
                rates_hz={f"t{k}": 30.0 for k in range(8)},
                duration_s=3.0,
                seed=20,
                result_timeout_s=60.0,
            )
            kt.join(timeout=10.0)
            # Loadgen-side books (per tenant) and router-side books
            # must BOTH balance — nothing silently dropped anywhere.
            assert report.accounting_ok()
            s = fleet.stats()
            assert _books_balance(s), s
            agg = sum(r.num_offered for r in report.tenants.values())
            assert s["aggregate_offered"] == agg
            # The kill actually happened and was recovered within the
            # restart budget.
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                s = fleet.stats()
                if s["restarts_total"] >= 1 and s["healthy_planes"] == 4:
                    break
                time.sleep(0.1)
            assert s["restarts_total"] >= 1
            assert s["healthy_planes"] == 4
            assert fleet.plane_pids()["plane1"] != killed["pid"]
            # Merged p99 through the degraded window.
            assert s["fleet_latency_count"] > 0
            assert s["fleet_p99_latency_s"] is not None
            # The storm actually spread: every plane completed work.
            assert all(p["completed"] > 0
                       for p in s["planes"].values())
        finally:
            fleet.close()
        assert fleet.accounting_ok()


class TestSpawnBudget:
    @pytest.mark.slow
    def test_spawn_fault_exhaustion_evicts_loudly(self, shipment):
        """Every respawn attempt fails (injected error rule at
        "fleet.plane.spawn"): the restart budget burns down to a LOUD
        permanent eviction while the surviving plane keeps serving and
        the books stay exact."""
        _fitted, _plan, X, ship = shipment
        fleet = _fleet(ship, num_planes=2, restart_budget=2,
                       heartbeat_interval_s=0.05)
        chaos = FaultPlan([
            FaultRule("fleet.plane.spawn", "error", p=1.0),
        ])
        try:
            fleet.submit(X[0]).result(timeout=30)
            with chaos:
                os.kill(fleet.plane_pids()["plane0"], signal.SIGKILL)
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    s = fleet.stats()
                    if s["evicted_planes"]:
                        break
                    time.sleep(0.1)
            s = fleet.stats()
            assert s["evicted_planes"] == ["plane0"]
            assert s["healthy_planes"] == 1
            assert s["planes"]["plane0"]["restart_budget_left"] == 0
            # Both budgeted attempts fired through the fault site.
            assert chaos.calls_seen("fleet.plane.spawn") >= 2
            # The survivor still serves; the books still balance.
            fleet.submit(X[0]).result(timeout=30)
            assert _books_balance(fleet.stats())
        finally:
            fleet.close()
        assert fleet.accounting_ok()


class TestQuarantine:
    @pytest.mark.slow
    def test_corrupted_ship_quarantines_plane(self, shipment):
        """Ship a plan whose weight plane is corrupted in transit (the
        "fleet.rpc.send" corrupt rule, installed in the CHILD via
        KEYSTONE_FAULT_PLAN): the plane boots QUARANTINED — it
        heartbeats, refuses traffic with a named error, and never
        serves wrong bits."""
        _fitted, _plan, X, ship = shipment
        spec = json.dumps({
            "rules": [{"site": "fleet.rpc.send", "kind": "corrupt",
                       "p": 1.0}],
            "seed": 0,
        })
        os.environ["KEYSTONE_FAULT_PLAN"] = spec
        try:
            fleet = _fleet(ship, num_planes=1)
        finally:
            os.environ.pop("KEYSTONE_FAULT_PLAN", None)
        try:
            s = fleet.stats()
            assert s["quarantined_planes"] == ["plane0"]
            assert s["healthy_planes"] == 0  # quarantined != eligible
            # The plane process is alive and heartbeating...
            assert fleet.plane_pids()["plane0"] is not None
            # ...but the fleet refuses to route to it, loudly.
            with pytest.raises(FleetPlaneDied, match="quarantined"):
                fleet.submit(X[0])
            s = fleet.stats()
            assert _books_balance(s)
            assert s["failed"] == 1
        finally:
            fleet.close()


class TestCanaryRoll:
    @pytest.mark.slow
    def test_offer_canary_rolls_across_fleet(self, shipment):
        """A candidate ships to every surviving plane and runs each
        plane's OWN lifecycle gate → canary → promotion; the fleet
        reports the new fingerprint everywhere afterwards."""
        _fitted, _plan, X, ship = shipment
        fitted2, _X2 = fit_tiny_mnist(seed=3)
        plan2 = export_plan(
            fitted2, np.zeros(TINY_D_IN, np.float32), max_batch=8
        )
        assert plan2.fingerprint != ship.fingerprint
        ship2 = encode_plan_ship(fitted2, plan2)
        fleet = _fleet(ship, num_planes=2, replicas_per_plane=2)
        try:
            for i in range(10):
                fleet.submit(X[i % len(X)]).result(timeout=30)
            results = fleet.offer_canary(ship2)
            assert set(results) == {"plane0", "plane1"}
            for name, r in results.items():
                assert r["ok"], (name, r)
                assert r["result"]["published"], (name, r)
                assert r["result"]["fingerprint"] == plan2.fingerprint
            # Post-roll traffic serves under the NEW fingerprint.
            y = fleet.submit(X[0])
            y.result(timeout=30)
            stats = fleet.stats()
            assert _books_balance(stats)
        finally:
            fleet.close()

    @pytest.mark.slow
    def test_corrupt_candidate_rejected_fleet_unharmed(self, shipment):
        """A tampered CANDIDATE ship is rejected per-plane by the same
        CRC verification as boot; the incumbent keeps serving."""
        _fitted, _plan, X, ship = shipment
        bad = copy.deepcopy(ship)
        t = bad.tensors[0]
        plane = t.raw if t.raw is not None else t.hi
        plane.flat[0] ^= 1
        fleet = _fleet(ship, num_planes=1)
        try:
            results = fleet.offer_canary(bad)
            assert results["plane0"]["ok"] is False
            assert results["plane0"]["error"] == "ship_rejected"
            fleet.submit(X[0]).result(timeout=30)  # incumbent intact
        finally:
            fleet.close()


class TestAdmission:
    def test_router_bound_sheds_with_named_rejection(self, shipment):
        """The router's own admission bound: past ``max_outstanding``
        submissions shed synchronously with FleetSaturated (a NAMED
        rejection, counted in the books)."""
        _fitted, _plan, X, ship = shipment
        fleet = _fleet(ship, num_planes=1, max_outstanding=4,
                       dispatchers=1)
        try:
            futs, rejected = [], 0
            for i in range(64):
                try:
                    futs.append(fleet.submit(X[i % len(X)]))
                except FleetSaturated:
                    rejected += 1
            for f in futs:
                f.exception(timeout=30)
            assert rejected >= 1
            s = fleet.stats()
            assert s["rejected"] >= rejected
            assert _books_balance(s)
        finally:
            fleet.close()
        assert fleet.accounting_ok()
