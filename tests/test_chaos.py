"""Chaos plane (loro_tpu/chaos/, docs/RESILIENCE.md "Chaos plane").

Tier-1 coverage for ISSUE 13:

- fault-site registry: ``faultinject.sites()``, typed rejection of
  unknown sites/actions and malformed ``LORO_FAULT`` entries, and the
  docs/registry cross-check (every site named in the docs is
  registered, and vice versa)
- plan determinism: same config => byte-identical step traces; typed
  config/step validation
- the chaos smoke: small seeds over the fully composed stack
  (sharded + tiered + durable group-commit + SyncServer sessions + a
  live WAL-shipping follower) must report zero invariant violations
- the determinism gate: two full runs of one seed produce the same
  trace bytes and the same invariant verdicts
- planted-violation pipeline: a synthetic reference-oracle corruption
  is caught at the next barrier, its artifact replays to the same
  violation, and the ddmin shrinker reduces the schedule to <= 25% of
  the original
- in-process resume: a second runner over the same durable root
  regenerates the reference oracle from the journal and finishes the
  plan clean
- the WAL-retention regressions the chaos plane found (chaos seed 4):
  marker-only segments must not be pruned out from under a pinned
  follower, and every family batch ticks its epoch clock per appended
  round

The SIGKILL orchestration (real crash children around the runner's
hold points) lives in tests/soak_chaos.py; the crash-during-checkpoint
composition corner is TestShardedTieredCheckpointCrash below (its
subprocess is a CPU-mesh child: a process that holds a chip is
never signalled).
"""
import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

from loro_tpu.chaos import (
    ChaosConfig,
    ChaosRunner,
    Step,
    generate_plan,
    load_artifact,
    replay_artifact,
    shrink_artifact,
    trace_json,
)
from loro_tpu.chaos.replay import reproduces
from loro_tpu.errors import ChaosError, ConfigError
from loro_tpu.resilience import faultinject

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every fault site the stack documents (docs/RESILIENCE.md "Fault
#: injection" is the canonical catalogue)
ALL_SITES = {
    "launch", "fetch", "decode", "poison_doc",
    "wal_write", "wal_torn_tail", "ckpt_corrupt",
    "sync_push", "sync_pull", "session_stall",
    "read_batch", "export_launch",
    "evict_flush", "revive_replay",
    "repl_ship", "repl_apply", "repl_promote",
    "net_accept", "net_frame", "conn_stall",
    "health_tick",
}

DOC_FILES = [
    "docs/RESILIENCE.md", "docs/PERSISTENCE.md", "docs/SYNC.md",
    "docs/REPLICATION.md", "docs/RESIDENCY.md", "docs/NET.md",
    "CLAUDE.md",
]


class TestFaultSiteRegistry:
    def test_catalogue_is_complete(self):
        sites = faultinject.sites()
        assert set(sites) == ALL_SITES
        for name, info in sites.items():
            assert info["help"], f"site {name} registered without help text"
            assert info["modules"], f"site {name} has no owning module"

    def test_unknown_site_raises_typed(self):
        with pytest.raises(ConfigError) as ei:
            faultinject.inject("wal_wirte")  # the motivating typo
        assert "wal_wirte" in str(ei.value)
        assert "wal_write" in str(ei.value)  # accepted set is spelled out
        assert not faultinject.active()

    def test_unknown_action_raises_typed(self):
        with pytest.raises(ConfigError) as ei:
            faultinject.inject("wal_write", action="explode")
        assert "explode" in str(ei.value)
        assert not faultinject.active()

    @pytest.mark.faultinject
    def test_env_entries_malformed_raise_typed(self):
        for bad in (
            "wal_wirte:raise",          # typo'd site
            "wal_write:explode",        # unknown action
            "wal_write:raise:bogus=1",  # unknown key
            "wal_write:raise:times=x",  # non-integer value
            "wal_write:raise=7",        # =value on a non-valued action
        ):
            with pytest.raises(ConfigError):
                faultinject._install_env_entry(bad)
            assert not faultinject.active(), bad
        # a well-formed entry still arms
        faultinject._install_env_entry("wal_write:raise:times=2")
        try:
            assert faultinject.active() == {"wal_write": 1}
        finally:
            faultinject.clear()

    def test_docs_and_registry_agree(self):
        """Both directions: every registered site is documented, and
        every site the docs claim exists is registered (a typo'd name
        in either place fails here)."""
        texts = {p: open(os.path.join(REPO, p)).read() for p in DOC_FILES}
        registered = set(faultinject.sites())
        for name in registered:
            hits = [p for p, t in texts.items() if f"`{name}`" in t]
            assert hits, f"registered fault site {name} appears in no doc"
        # doc-claimed sites: backticked snake_case tokens in the same
        # sentence as "fault site(s)" / the RESILIENCE.md "Sites:" list
        claimed = set()
        for t in texts.values():
            for m in re.finditer(r"[Ff]ault sites?\b([^.;(]{0,220})", t):
                claimed.update(re.findall(r"`([a-z][a-z_]+)`", m.group(1)))
            for m in re.finditer(r"`([a-z][a-z_]+)`[^.\n]{0,40}fault site", t):
                claimed.add(m.group(1))
        m = re.search(r"Sites:\n(.*?)\n\n", texts["docs/RESILIENCE.md"], re.S)
        assert m, "docs/RESILIENCE.md lost its fault-site catalogue"
        claimed.update(re.findall(r"`([a-z][a-z_]+)`\s*\(", m.group(1)))
        claimed.discard("faultinject")  # the module, not a site
        unknown = claimed - registered
        assert not unknown, (
            f"docs name fault sites that are not registered: {sorted(unknown)}"
        )


class TestPlan:
    def test_same_config_same_trace_bytes(self):
        cfg = ChaosConfig(seed=9, steps=30)
        a, b = generate_plan(cfg), generate_plan(ChaosConfig(seed=9, steps=30))
        assert trace_json(a) == trace_json(b)
        c = generate_plan(ChaosConfig(seed=10, steps=30))
        assert trace_json(a) != trace_json(c)

    def test_plant_at_emits_plant_step(self):
        cfg = ChaosConfig(seed=1, steps=10, plant_at=3)
        kinds = [s.kind for s in generate_plan(cfg)]
        assert "plant" in kinds
        assert "plant" not in [
            s.kind for s in generate_plan(ChaosConfig(seed=1, steps=10))]

    def test_barriers_every_and_final(self):
        plan = generate_plan(ChaosConfig(seed=2, steps=21, barrier_every=10))
        assert plan[-1].kind == "check"
        assert sum(1 for s in plan if s.kind == "check") >= 3

    def test_config_validation_typed(self):
        with pytest.raises(ConfigError):
            ChaosConfig(families=("text", "blob"))
        with pytest.raises(ConfigError):
            ChaosConfig(steps=0)
        with pytest.raises(ConfigError):
            ChaosConfig(docs=0)

    def test_malformed_step_and_config_json_typed(self):
        with pytest.raises(ChaosError):
            Step.from_json({"kind": "edit"})  # no index
        with pytest.raises(ChaosError):
            ChaosConfig.from_json({"seed": 1, "bogus_knob": 2})

    def test_artifact_loader_rejects_garbage(self, tmp_path):
        p = tmp_path / "art.json"
        p.write_text("{not json")
        with pytest.raises(ChaosError):
            load_artifact(str(p))
        p.write_text(json.dumps({"version": 999}))
        with pytest.raises(ChaosError):
            load_artifact(str(p))


def _small_cfg(**kw) -> ChaosConfig:
    """The planted/determinism/resume config: single family, no
    follower — the cheapest stack that still runs the full runner
    machinery (ShardedResidentServer + durable WAL + SyncServer)."""
    base = dict(seed=77, steps=8, families=("map",), docs=2, shards=1,
                hot_slots=None, sessions=2, barrier_every=4,
                follower=False)
    base.update(kw)
    return ChaosConfig(**base)


class TestChaosSmoke:
    """The tier-1 chaos smoke: small seeds over the fully composed
    stack — sharded + tiered + durable group-commit + sync sessions +
    a live follower.  Zero invariant violations is the acceptance
    gate; seeds/families chosen to keep the smoke within the tier-1
    budget while covering tier churn, migration and replication arms.
    """

    @pytest.mark.parametrize("seed,families", [
        (101, ("text", "map")),
        (202, ("counter", "movable")),
        (303, ("tree",)),
    ])
    def test_composed_stack_clean(self, tmp_path, seed, families):
        cfg = ChaosConfig(
            seed=seed, steps=14, families=families, docs=3, shards=2,
            hot_slots=1, sessions=2, barrier_every=7, follower=True,
        )
        report = ChaosRunner(cfg, str(tmp_path)).run()
        assert report.clean, [v.to_json() for v in report.violations]
        assert report.checks >= 2
        assert not report.held

    def test_kill_step_downgrades_in_process(self, tmp_path):
        """A ``kill`` step without an orchestrating parent executes as
        reopen-on-every-family (counted) so plans stay replayable."""
        from loro_tpu.obs import metrics as obs

        cfg = _small_cfg(seed=5)
        plan = [
            Step(i=0, kind="edit", params={"client": 1, "seed": 11, "ops": 2}),
            Step(i=1, kind="kill"),
            Step(i=2, kind="edit", params={"client": 2, "seed": 12, "ops": 2}),
            Step(i=3, kind="check"),
        ]
        before = obs.counter("chaos.kill_downgraded_total").total()
        report = ChaosRunner(cfg, str(tmp_path)).run(plan)
        assert report.clean, [v.to_json() for v in report.violations]
        assert obs.counter("chaos.kill_downgraded_total").total() == before + 1


class TestDeterminismGate:
    def test_two_runs_same_trace_and_verdicts(self, tmp_path):
        """Same seed => byte-identical step trace and identical
        invariant verdicts across two independent runs (fresh durable
        roots).  Run with a planted violation so verdict equality is
        non-trivial."""
        cfg = _small_cfg(plant_at=2)
        r1 = ChaosRunner(cfg, str(tmp_path / "a")).run()
        r2 = ChaosRunner(_small_cfg(plant_at=2), str(tmp_path / "b")).run()
        assert r1.trace_json() == r2.trace_json()
        assert not r1.clean and not r2.clean
        assert sorted(v.key() for v in r1.violations) == \
            sorted(v.key() for v in r2.violations)
        assert r1.steps_run == r2.steps_run


class TestPlantedViolationPipeline:
    def test_catch_replay_shrink(self, tmp_path):
        """The acceptance pipeline: a planted reference-oracle
        corruption is caught by the checker, the artifact replays to
        the same violation, and ddmin shrinks the schedule to <= 25%
        of the original."""
        cfg = _small_cfg(plant_at=2)
        runner = ChaosRunner(cfg, str(tmp_path / "run"))
        report = runner.run()
        # caught: the planted divergence breaks convergence invariants
        assert not report.clean
        keys = {v.key() for v in report.violations}
        assert ("convergence", "map") in keys
        assert os.path.exists(runner.artifact_path)
        art = load_artifact(runner.artifact_path)
        assert art["verdict"] == "violation"
        # replays deterministically to the same violation
        rep2, expected = replay_artifact(
            runner.artifact_path, str(tmp_path / "replay"))
        assert reproduces(rep2, expected), (
            sorted(v.key() for v in rep2.violations), expected)
        # shrinks to the minimal schedule (plant + barrier)
        out = shrink_artifact(runner.artifact_path,
                              str(tmp_path / "min.json"),
                              work_dir=str(tmp_path / "probes"))
        st = out["shrink"]
        assert st["shrunk_steps"] <= max(2, st["original_steps"] * 0.25), st
        kinds = [s["kind"] for s in out["trace"]]
        assert "plant" in kinds and kinds[-1] == "check"
        # the minimized artifact still reproduces
        rep3, exp3 = replay_artifact(out["path"], str(tmp_path / "replay2"))
        assert reproduces(rep3, exp3)

    def test_shrink_refuses_clean_artifact(self, tmp_path):
        art = {"version": 1, "config": _small_cfg().to_json(),
               "trace": [], "violations": []}
        p = tmp_path / "clean.json"
        p.write_text(json.dumps(art))
        with pytest.raises(ChaosError):
            shrink_artifact(str(p))


class TestResume:
    def test_in_process_resume_regenerates_oracle(self, tmp_path):
        """A second runner over the same durable root: recovers the
        stack from disk, rebuilds the reference oracle purely from the
        journal, and finishes the plan clean — the crash-side half the
        SIGKILL soak exercises with real kills."""
        cfg = _small_cfg(seed=31, steps=10, barrier_every=5)
        plan = generate_plan(cfg)
        mid = next(s.i for s in plan if s.kind == "check") + 1
        # segment 1 executes steps i < mid and closes gracefully (the
        # soak's SIGKILL version crashes here instead)
        r1 = ChaosRunner(cfg, str(tmp_path)).run(plan[:mid])
        assert r1.clean
        r2 = ChaosRunner(cfg, str(tmp_path)).run(plan, resume_from=mid)
        assert r2.clean, [v.to_json() for v in r2.violations]
        assert r2.checks >= 1


class TestWalRetentionRegressions:
    """The two product bugs chaos seed 4 found (see CHANGES.md PR 13):
    both must stay fixed."""

    def test_marker_only_segments_survive_follower_pin(self, tmp_path):
        """A marker-only WAL segment (e.g. sealed by the epoch-0
        auto-checkpoint right after a follower attaches) must NOT be
        pruned while a fresh follower pin is active — pruning it
        punches a hole in the shipped stream and orphans the follower
        typed.  Without a pin the old behavior stands."""
        from loro_tpu.persist.wal import WriteAheadLog

        def build(d):
            w = WriteAheadLog(str(d))
            w.append_ckpt_marker(0, "ckpt-0")  # marker-only seg-1
            w.rotate()
            w.append_round(1, None, [b"x"])    # seg-2: a real round
            w.append_ckpt_marker(1, "ckpt-1")
            w.rotate()                          # seg-3 active
            return w

        pinned = build(tmp_path / "pinned")
        pinned.retention_floor = lambda: 0  # fresh follower, acked 0
        assert pinned.prune_below(1) == 0   # everything pinned
        assert [i.index for i in pinned._segments] == [1, 2, 3]
        pinned.close()

        free = build(tmp_path / "free")     # no replication: old rules
        assert free.prune_below(1) == 2
        assert [i.index for i in free._segments] == [3]
        free.close()

    def test_acked_follower_pin_is_prefix_contiguous(self, tmp_path):
        """With a follower acked at epoch 1, rounds <= 1 prune but the
        marker-only segment BETWEEN kept segments survives — the
        shipped stream must stay contiguous."""
        from loro_tpu.persist.wal import WriteAheadLog

        w = WriteAheadLog(str(tmp_path / "wal"))
        w.append_round(1, None, [b"a"])
        w.rotate()                       # seg-1 sealed (round 1)
        w.append_ckpt_marker(1, "c1")
        w.rotate()                       # seg-2 sealed (marker-only)
        w.append_round(2, None, [b"b"])
        w.rotate()                       # seg-3 sealed (round 2)
        w.retention_floor = lambda: 1
        assert w.prune_below(2) == 1     # only seg-1 goes
        assert [i.index for i in w._segments] == [2, 3, 4]
        w.close()

    def test_every_family_batch_ticks_epoch_per_round(self):
        """The journal-epoch contract: every appended round advances
        the batch clock, even when the round stages nothing for this
        family (a tree server fed a map-only edit).  A lazy clock
        stamped those rounds' WAL records with epoch 0 / duplicate
        epochs — invisible to recovery replay and fatal to follower
        retention pins."""
        from loro_tpu.parallel.fleet import (
            DeviceCounterBatch,
            DeviceDocBatch,
            DeviceMapBatch,
            DeviceMovableBatch,
            DeviceTreeBatch,
        )

        batches = {
            "text": DeviceDocBatch(1, capacity=64),
            "map": DeviceMapBatch(1, slot_capacity=8),
            "tree": DeviceTreeBatch(1, move_capacity=32, node_capacity=8),
            "movable": DeviceMovableBatch(1, capacity=32, elem_capacity=8),
            "counter": DeviceCounterBatch(1, slot_capacity=4),
        }
        for fam, b in batches.items():
            before = b.epoch
            if fam in ("map", "counter"):
                b.append_changes([None])
            else:
                b.append_changes([None], None)
            assert b.epoch == before + 1, (
                f"{fam} batch did not tick its epoch clock for an "
                "empty round")


class TestShardedTieredCheckpointCrash:
    """ISSUE 13 satellite: SIGKILL during ``checkpoint()`` on a
    sharded + tiered + durable server (cold-doc rung rewrite
    mid-flight), then ``recover_sharded_server`` — all docs readable,
    tier map consistent, ``durable_epoch`` correct.  The child is a
    CPU-mesh process (never signal a process that holds a chip)."""

    def test_crash_mid_checkpoint_recovers(self, tmp_path):
        child = os.path.join(REPO, "tests", "_chaos_ckpt_crash_child.py")
        base = str(tmp_path)
        proc = subprocess.Popen(
            [sys.executable, child, base],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        ready = os.path.join(base, "READY")
        try:
            deadline = time.time() + 300
            while not os.path.exists(ready):
                if proc.poll() is not None:
                    out = proc.stdout.read().decode(errors="replace")
                    pytest.fail(f"crash child exited early:\n{out[-3000:]}")
                if time.time() > deadline:
                    pytest.fail("crash child never reached the hold point")
                time.sleep(0.1)
            # the child is inside checkpoint(), hung at the armed
            # ckpt_corrupt fault (rung rewrite mid-flight)
            time.sleep(0.5)
            os.kill(proc.pid, signal.SIGKILL)
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()

        from tests import _chaos_ckpt_crash_child as cc

        srv_dir = os.path.join(base, "text")
        # a torn rung tmp (what a crash mid-write leaves) must be inert
        with open(os.path.join(
                srv_dir, "shard-00", "ckpt", "ckpt-99999999.tmp"), "wb") as f:
            f.write(b"torn rung bytes")

        import io

        from loro_tpu.persist import recover_sharded_server
        from loro_tpu.persist.inspect import inspect_dir

        buf = io.StringIO()
        assert inspect_dir(srv_dir, out=buf) == 0, buf.getvalue()

        srv = recover_sharded_server(srv_dir)
        try:
            prog = cc.read_progress(base)
            assert prog["cold_docs"], "child demoted nothing — vacuous test"
            assert srv.durable_epoch == prog["durable_epoch"], (
                srv.durable_epoch, prog)
            # tier map consistent: the demoted docs came back cold,
            # backed by the surviving (pre-crash) rung
            cold = set()
            for s in srv.shards:
                tiers = s.residency.tiers()
                cold.update(srv._globals_of(srv.shards.index(s),
                                            tiers.get("cold", [])))
            assert cold == set(prog["cold_docs"]), (cold, prog)
            # all docs readable and byte-right vs the deterministic
            # oracle (reading revives cold docs through the rung+tail)
            oracle = cc.build_oracle(prog["rounds"])
            texts = srv.texts()
            for di in range(cc.DOCS):
                assert texts[di] == oracle[di], f"doc {di} diverged"
        finally:
            srv.close()
