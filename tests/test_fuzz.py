"""Generative history & interleaving fuzzer (ISSUE 15).

The contract under test (gen/fuzz.py, gen/shrink.py, gen/interleave.py):

- GRAMMAR: the seeded walker composes ALL 13 decision types plus the
  arrival/transient/close surface into legal histories, byte-identical
  per (seed, workflow_index) — the coverage counter is the acceptance
  counter, the digest is the reproducibility witness.
- PARITY: every generated corpus replays with zero oracle↔device
  divergence on the dense and wirec paths, through verify_all
  (resident/ladder engine tier, mesh-of-1 AND sharded), and through
  NDC two-branch conflict forks (replay_tree_payloads arbitration).
- SHRINKING: an injected divergence on a 200-event history reduces to a
  ≤3-batch witness that reproduces from the reported seed alone.
- INTERLEAVING: a seeded live-transaction schedule against a durable
  serving-enabled Onebox under op chaos + store faults + crashpoint
  kills converges to checksums byte-identical to a fault-free run, with
  tpu.serving/parity-divergence == 0 and a clean recovery fsck at every
  kill.
- PROMOTION: `fuzz promote` specs regenerate byte-identically (drift
  guarded by digest) as permanent corpora.
"""
import numpy as np
import pytest

from cadence_tpu.core.checksum import DEFAULT_LAYOUT, payload_row
from cadence_tpu.core.enums import DecisionType
from cadence_tpu.gen import fuzz, shrink
from cadence_tpu.gen.corpus import generate_corpus

pytestmark = pytest.mark.fuzz


class TestGrammar:
    def test_reproducible_byte_identical(self):
        """Same (seed, workflow_index) → byte-identical history; a
        different index or seed perturbs it."""
        a = fuzz.generate_fuzz_history(9, 2, 120)
        b = fuzz.generate_fuzz_history(9, 2, 120)
        assert fuzz.history_digest(a) == fuzz.history_digest(b)
        assert (fuzz.history_digest(a)
                != fuzz.history_digest(fuzz.generate_fuzz_history(9, 3, 120)))
        assert (fuzz.history_digest(a)
                != fuzz.history_digest(fuzz.generate_fuzz_history(10, 2, 120)))

    def test_fifty_seed_corpus_covers_all_13_decision_types(self):
        """The acceptance counter: 50 seeds (profiles rotating) emit
        evidence events for every DecisionType member."""
        histories = [
            fuzz.generate_fuzz_history(seed, 0, 80,
                                       fuzz.PROFILES[seed % len(fuzz.PROFILES)])
            for seed in range(50)
        ]
        cov = fuzz.coverage(histories)
        assert not cov["missing_decisions"], cov["missing_decisions"]
        assert set(cov["decisions"]) == {d.name for d in DecisionType}
        assert len(cov["decisions"]) == 13

    def test_corpus_suite_addressing(self):
        """generate_corpus("fuzz:<profile>") routes to the fuzzer — the
        addressing every downstream consumer (bench, specs) speaks."""
        via_suite = generate_corpus("fuzz:signal_storm", 2, seed=4,
                                    target_events=60)
        direct = fuzz.generate_fuzz_corpus(2, seed=4, target_events=60,
                                           profile="signal_storm")
        assert ([fuzz.history_digest(h) for h in via_suite]
                == [fuzz.history_digest(h) for h in direct])

    def test_unknown_profile_rejected(self):
        with pytest.raises(ValueError):
            fuzz.generate_fuzz_history(1, 0, 50, profile="nope")

    def test_capacities_respected(self):
        """The walker keeps every pending table within the payload
        layout — generated corpora exercise the BASE kernel, never the
        overflow suite's fallback path."""
        from cadence_tpu.core.enums import EventType
        for seed in range(6):
            h = fuzz.generate_fuzz_history(seed, 0, 150)
            pend = {k: 0 for k in ("act", "timer", "child")}
            peak = dict(pend)
            for b in h:
                for e in b.events:
                    et = e.event_type
                    if et == EventType.ActivityTaskScheduled:
                        pend["act"] += 1
                    elif et in (EventType.ActivityTaskCompleted,
                                EventType.ActivityTaskFailed,
                                EventType.ActivityTaskTimedOut,
                                EventType.ActivityTaskCanceled):
                        pend["act"] -= 1
                    elif et == EventType.TimerStarted:
                        pend["timer"] += 1
                    elif et in (EventType.TimerFired,
                                EventType.TimerCanceled):
                        pend["timer"] -= 1
                    for k in pend:
                        peak[k] = max(peak[k], pend[k])
            assert peak["act"] <= DEFAULT_LAYOUT.max_activities
            assert peak["timer"] <= DEFAULT_LAYOUT.max_timers


class TestHistoryParity:
    def test_parity_run_smoke(self):
        """The bounded tier-1 sweep: dense + wirec + verify_all + NDC
        forks over every profile, zero divergence, full decision
        coverage asserted by the driver itself."""
        doc = fuzz.parity_run(seeds=7, workflows_per_seed=2,
                              target_events=80)
        assert doc["ok"], {k: doc[k] for k in (
            "dense_divergent", "wirec_divergent", "device_errors",
            "verify_divergent", "ndc_divergent", "missing_decisions")}
        assert doc["workflows"] == 14
        assert doc["ndc_forked"] > 0

    def test_verify_all_sharded_matches_mesh_of_1(self):
        """The engine tier on the conftest 8-device mesh: sharded
        verify_all and mesh-of-1 verify_all agree (both clean) over one
        fuzz corpus — the serving-mesh configuration of the parity
        driver."""
        import jax

        from cadence_tpu.engine.persistence import Stores
        from cadence_tpu.engine.tpu_engine import TPUReplayEngine
        from cadence_tpu.parallel.mesh import make_mesh

        hists = fuzz.generate_fuzz_corpus(12, seed=21, target_events=70)
        for devices in (1, 4):
            stores = Stores()
            keys = fuzz.seed_stores(stores, hists)
            engine = TPUReplayEngine(
                stores, chunk_workflows=8,
                mesh=make_mesh(jax.devices()[:devices]))
            result = engine.verify_all(keys)
            assert result.ok, (devices, result.divergent)
            assert result.verified_on_device + len(result.fallback) \
                == result.total

    @pytest.mark.slow
    def test_wide_sweep(self):
        """The full 50-seed acceptance corpus (also run by
        deploy/smoke_fuzz.sh via the CLI)."""
        doc = fuzz.parity_run(seeds=50, workflows_per_seed=4,
                              target_events=100)
        assert doc["ok"]
        assert not doc["missing_decisions"]


class TestShrinker:
    def test_injected_divergence_shrinks_to_minimal_batches(self):
        """ISSUE 15 satellite: a planted device-side defect on a
        200-event generated history must shrink to ≤3 batches and stay
        reproducible from the reported seed."""
        poison = shrink.inject_poison_signal(5, 0, target_events=200)
        assert poison, "seed 5 emitted no signals — pick another seed"
        pred = shrink.poisoned_parity_predicate(poison)
        report = shrink.shrink_history(5, 0, pred, target_events=200)
        assert report.original_events >= 150
        assert report.shrunk_batches <= 3, report.summary()
        # reproducibility: the minimal slice regenerates from the seed
        minimal = report.reproduce()
        assert shrink.history_digest(minimal) == report.digest
        assert pred(minimal), "reproduced slice no longer fails"
        # 1-minimality: dropping any kept batch kills the failure
        for i in range(len(minimal)):
            assert not pred(minimal[:i] + minimal[i + 1:])

    def test_shrink_rejects_non_failing_input(self):
        with pytest.raises(ValueError):
            shrink.shrink_batches(
                fuzz.generate_fuzz_history(3, 0, 60), lambda b: False)

    def test_real_parity_predicate_clean_on_generated(self):
        """The non-poisoned predicate finds nothing to chase on a clean
        corpus (so `fuzz shrink` without --poison is a no-op today —
        the kernel has no known divergence)."""
        pred = shrink.parity_predicate()
        assert not pred(fuzz.generate_fuzz_history(2, 0, 60))


class TestInterleaving:
    def test_zero_divergence_under_combined_chaos(self):
        """The serving-tier acceptance bar: one seeded schedule, run
        fault-free then under op chaos + store faults + crashpoint
        kills — final checksums byte-identical, parity-divergence 0,
        recovery fsck clean at every kill, closing verify_all clean."""
        from cadence_tpu.gen.interleave import interleave_scenario

        doc = interleave_scenario(
            seed=11, num_workflows=3, length=20, kills=2,
            chaos_spec="drop=0.05,delay=0.05,delay_ms=1,seed=5",
            store_fault_rate=0.04)
        assert doc["ok"], doc
        assert doc["checksums_identical"]
        chaos = doc["chaos"]
        assert chaos["kills_fired"] >= 1
        assert chaos["kills_fired"] == chaos["fsck_clean"]
        assert not chaos["fsck_findings"]
        assert chaos["parity_divergence"] == 0
        assert chaos["serving_transactions"] > 0
        assert chaos["verify_divergent"] == 0
        # the fault families actually fired (the run is not vacuous)
        assert chaos["retries"] > 0
        assert chaos["op_drops"] + chaos["store_faults"] > 0

    def test_schedule_reproducible(self):
        from cadence_tpu.gen.interleave import build_schedule

        assert build_schedule(3, 4, 50, 2) == build_schedule(3, 4, 50, 2)
        assert build_schedule(3, 4, 50, 2) != build_schedule(4, 4, 50, 2)

    @pytest.mark.slow
    def test_wide_interleaving(self):
        from cadence_tpu.gen.interleave import interleave_scenario

        for seed in (7, 23):
            doc = interleave_scenario(
                seed=seed, num_workflows=4, length=60, kills=3,
                chaos_spec="drop=0.05,delay=0.08,delay_ms=2,seed=3",
                store_fault_rate=0.04)
            assert doc["ok"], (seed, doc["chaos"])


class TestPromotion:
    def test_spec_roundtrip_and_drift_guard(self, tmp_path):
        spec = fuzz.make_spec("adversarial-1", seed=13, workflows=4,
                              target_events=60, profile="ndc_conflict",
                              note="found by sweep r01")
        path = fuzz.save_spec(spec, root=str(tmp_path))
        assert path.endswith("fuzz_specs/adversarial-1.json")
        loaded = fuzz.load_specs(str(tmp_path))
        assert [s.name for s in loaded] == ["adversarial-1"]
        histories = loaded[0].generate()
        assert len(histories) == 4
        assert fuzz.history_digest(histories[0]) == spec.digest
        # drift guard: a tampered digest refuses to regenerate
        import dataclasses
        bad = dataclasses.replace(loaded[0], digest="0" * 64)
        with pytest.raises(ValueError):
            bad.generate()

    def test_promoted_spec_parity(self, tmp_path):
        """A promoted corpus replays parity-clean."""
        spec = fuzz.make_spec("bench-feed", seed=3, workflows=6,
                              target_events=60, profile="chain")
        histories = spec.generate()
        from cadence_tpu.ops.replay import replay_corpus

        rows, _crcs, errors = replay_corpus(histories)
        expected = np.stack([fuzz.oracle_final_row(h) for h in histories])
        assert (errors == 0).all()
        assert (rows == expected).all()

    def test_cli_promote_then_run(self, tmp_path, capsys):
        """The operator loop: `fuzz promote` writes the spec; `fuzz
        shrink` on a clean history reports nothing to shrink."""
        from cadence_tpu.cli import main

        rc = main(["fuzz", "promote", "--name", "cli-spec", "--seed", "8",
                   "--workflows", "3", "--events", "50",
                   "--root", str(tmp_path)])
        assert rc == 0
        assert fuzz.load_specs(str(tmp_path))[0].name == "cli-spec"
        rc = main(["fuzz", "shrink", "--seed", "8", "--events", "50"])
        assert rc == 0


class TestOracleChainFollowing:
    def test_oracle_final_row_follows_continue_as_new(self):
        """A chain-profile history's device row is the NEW run's state
        (FLAG_RUN_RESET chaining); oracle_final_row must follow."""
        from cadence_tpu.oracle.state_builder import StateBuilder

        for seed in range(12):
            h = fuzz.generate_fuzz_history(seed, 0, 60, "chain")
            if not h[-1].new_run_events:
                continue
            sb = StateBuilder()
            sb.replay_history(h)
            assert sb.new_run_state is not None
            from cadence_tpu.core.checksum import STICKY_ROW_INDEX
            row = fuzz.oracle_final_row(h)
            direct = payload_row(sb.new_run_state)
            direct[STICKY_ROW_INDEX] = 0
            assert (row == direct).all()
            break
        else:
            pytest.skip("no chain-closing seed in range — widen it")
