"""tpulint + lock witness (ISSUE 9): per-rule fixture snippets (one
true positive and one clean snippet each), pragma/baseline behavior,
the repo-wide tier-1 gate (zero unsuppressed findings over loro_tpu/ +
chip_smoke.py), and the runtime lock-order witness — including the
deliberate-inversion test that proves the witness can fail."""
import json
import os
import subprocess
import sys

import pytest

from loro_tpu.analysis import lint_source, lint_paths
from loro_tpu.analysis.lint import DEFAULT_BASELINE
from loro_tpu.analysis.lockwitness import (
    named_lock,
    named_rlock,
    witness,
)
from loro_tpu.analysis import lockorder
from loro_tpu.errors import AnalysisError, LockOrderViolation, LoroError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return [f.rule for f in findings if not f.suppressed]


# ---------------------------------------------------------------------------
# per-rule fixtures
# ---------------------------------------------------------------------------


class TestRuleFixtures:
    def test_dev_rule_flags_unblessed_jax(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    y = jax.device_put(x)\n"
            "    return jnp.zeros(4) + y\n"
        )
        got = rules_of(lint_source(bad, path="loro_tpu/sync/fixture.py"))
        assert got == ["LT-DEV", "LT-DEV"]

    def test_dev_rule_clean_in_blessed_module_and_via_supervisor(self):
        bad = "import jax\n\ndef f(x):\n    return jax.device_put(x)\n"
        assert rules_of(lint_source(bad, path="loro_tpu/ops/fixture.py")) == []
        ok = (
            "from ..resilience import get_supervisor\n"
            "def f(thunk):\n"
            "    return get_supervisor().launch(thunk, label='fix')\n"
        )
        assert rules_of(lint_source(ok, path="loro_tpu/sync/fixture.py")) == []

    def test_pad_rule_flags_raw_device_shape(self):
        bad = (
            "import jax.numpy as jnp\n"
            "def f(rows):\n"
            "    return jnp.zeros((len(rows), 4))\n"
        )
        got = lint_source(bad, path="loro_tpu/parallel/fixture.py",
                          rules=["LT-PAD"])
        assert rules_of(got) == ["LT-PAD"]
        assert got[0].line == 3

    def test_pad_rule_flags_inline_device_put_staging(self):
        bad = (
            "import jax\nimport numpy as np\n"
            "def f(rows):\n"
            "    return jax.device_put(np.zeros((len(rows), 2)))\n"
        )
        # device_put itself is LT-DEV territory in parallel/ paths
        # outside fleet.py; the np ctor inside it is the LT-PAD half
        got = rules_of(lint_source(bad, path="loro_tpu/parallel/fixture.py",
                                   rules=["LT-PAD"]))
        assert got == ["LT-PAD"]

    def test_pad_rule_clean_through_pad_bucket_and_host_staging(self):
        ok = (
            "import jax.numpy as jnp\nimport numpy as np\n"
            "from ..ops.fugue_batch import pad_bucket\n"
            "def f(rows):\n"
            "    n = pad_bucket(len(rows))\n"
            "    host = np.zeros((len(rows), 4))  # host staging: exempt\n"
            "    return jnp.zeros((pad_bucket(len(rows)), 4)), host, n\n"
        )
        assert rules_of(lint_source(
            ok, path="loro_tpu/parallel/fixture.py", rules=["LT-PAD"]
        )) == []

    def test_hash_rule_flags_builtin_hash_and_global_random(self):
        bad = (
            "import random\n"
            "def place(key, n):\n"
            "    jitter = random.getrandbits(8)\n"
            "    return (hash(key) + jitter) % n\n"
        )
        got = rules_of(lint_source(bad, path="loro_tpu/persist/fixture.py"))
        assert sorted(got) == ["LT-HASH", "LT-HASH"]

    def test_hash_rule_clean_for_seeded_rng_dunder_and_other_paths(self):
        ok = (
            "import random\n"
            "class K:\n"
            "    def __hash__(self):\n"
            "        return hash(('k', 1))\n"
            "def noise():\n"
            "    return random.Random(0xA07).random()\n"
        )
        assert rules_of(lint_source(ok, path="loro_tpu/persist/fixture.py")) == []
        # outside placement/journal/wire scope the rule stays quiet
        bad = "def f(k, n):\n    return hash(k) % n\n"
        assert rules_of(lint_source(bad, path="loro_tpu/models/fixture.py")) == []

    def test_time_rule_flags_wall_clock_call(self):
        bad = (
            "import time\n"
            "def backoff(deadline):\n"
            "    return deadline - time.time()\n"
        )
        got = lint_source(bad, path="loro_tpu/resilience/fixture.py")
        assert rules_of(got) == ["LT-TIME"]

    def test_time_rule_clean_for_injected_clock_and_monotonic(self):
        ok = (
            "import time\n"
            "def backoff(deadline, clock=time.time):\n"
            "    return deadline - clock() + time.monotonic()\n"
        )
        assert rules_of(lint_source(ok, path="loro_tpu/resilience/fixture.py")) == []

    def test_exc_rule_flags_swallowing_catch_and_untyped_class(self):
        bad = (
            "class WireError(Exception):\n    pass\n"
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        got = rules_of(lint_source(bad, path="loro_tpu/sync/fixture.py"))
        assert sorted(got) == ["LT-EXC", "LT-EXC"]

    def test_exc_rule_clean_for_typed_wrap_and_rooted_class(self):
        ok = (
            "from ..errors import DecodeError, LoroError\n"
            "class WireError(LoroError, ValueError):\n    pass\n"
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception as e:\n"
            "        raise DecodeError(f'malformed: {e}') from e\n"
        )
        assert rules_of(lint_source(ok, path="loro_tpu/sync/fixture.py")) == []

    @pytest.mark.parametrize("path", [
        "loro_tpu/parallel/fixture.py", "loro_tpu/ops/fixture.py", "chip_smoke.py",
    ])
    def test_chip_rule_flags_every_way_to_signal_a_process(self, path):
        bad = (
            "import os, signal\n"
            "def f(pid, proc):\n"
            "    os.kill(pid, signal.SIGTERM)\n"
            "    proc.terminate()\n"
            "    proc.kill()\n"
            "    proc.send_signal(signal.SIGINT)\n"
        )
        got = rules_of(lint_source(bad, path=path, rules=["LT-CHIP"]))
        assert got == ["LT-CHIP"] * 4

    def test_chip_rule_clean_for_sync_and_sig0(self):
        ok = (
            "import os\nimport jax\n"
            "def f(out, pid):\n"
            "    jax.block_until_ready(out)  # synchronises on the chip\n"
            "    os.kill(pid, 0)  # existence probe, sends nothing\n"
        )
        assert rules_of(lint_source(ok, path="loro_tpu/parallel/fixture.py",
                                    rules=["LT-CHIP"])) == []

    def test_lock_rule_flags_inverted_static_nesting(self):
        bad = (
            "class S:\n"
            "    def f(self):\n"
            "        with self._epoch_lock:\n"
            "            with self._route_lock:\n"
            "                pass\n"
        )
        got = lint_source(bad, path="loro_tpu/parallel/fixture.py")
        assert rules_of(got) == ["LT-LOCK"]
        assert "sharded.route" in got[0].message

    def test_lock_rule_clean_for_declared_nesting(self):
        ok = (
            "class S:\n"
            "    def f(self):\n"
            "        with self._route_lock:\n"
            "            with self._dev_lock:\n"
            "                with self._epoch_lock:\n"
            "                    pass\n"
        )
        assert rules_of(lint_source(
            ok, path="loro_tpu/parallel/fixture.py", rules=["LT-CHIP"]
        )) == []


# ---------------------------------------------------------------------------
# pragmas + baseline
# ---------------------------------------------------------------------------


class TestPragmas:
    BAD = "import time\ndef f():\n    return time.time()\n"

    def test_trailing_pragma_suppresses_with_reason(self):
        src = self.BAD.replace(
            "return time.time()",
            "return time.time()  # tpulint: disable=LT-TIME(fixture reason)",
        )
        got = lint_source(src, path="loro_tpu/sync/fixture.py")
        assert [f.rule for f in got] == ["LT-TIME"]
        assert got[0].suppressed and got[0].reason == "fixture reason"

    def test_comment_line_pragma_covers_next_line(self):
        src = (
            "import time\n"
            "def f():\n"
            "    # tpulint: disable=LT-TIME(fixture reason)\n"
            "    return time.time()\n"
        )
        got = lint_source(src, path="loro_tpu/sync/fixture.py")
        assert len(got) == 1 and got[0].suppressed

    def test_reasonless_pragma_does_not_suppress_and_is_reported(self):
        src = self.BAD.replace(
            "return time.time()",
            "return time.time()  # tpulint: disable=LT-TIME",
        )
        got = lint_source(src, path="loro_tpu/sync/fixture.py")
        assert sorted(f.rule for f in got if not f.suppressed) == [
            "LT-PRAGMA", "LT-TIME",
        ]

    def test_unknown_rule_pragma_is_reported(self):
        src = "x = 1  # tpulint: disable=LT-BOGUS(nope)\n"
        got = lint_source(src, path="loro_tpu/sync/fixture.py")
        assert rules_of(got) == ["LT-PRAGMA"]

    def test_pragma_examples_in_docstrings_are_prose(self):
        src = (
            '"""Docs show `# tpulint: disable=RULE(reason)` usage."""\n'
            "x = 1\n"
        )
        assert lint_source(src, path="loro_tpu/sync/fixture.py") == []

    def test_multi_rule_pragma(self):
        src = (
            "import time, jax\n"
            "def f():\n"
            "    return jax.devices(), time.time()  "
            "# tpulint: disable=LT-DEV(fixture a), LT-TIME(fixture b)\n"
        )
        got = lint_source(src, path="loro_tpu/sync/fixture.py")
        assert all(f.suppressed for f in got) and len(got) == 2
        assert {f.reason for f in got} == {"fixture a", "fixture b"}


class TestBaseline:
    def test_baseline_tolerates_known_finding(self, tmp_path):
        bad_dir = tmp_path / "loro_tpu" / "sync"
        bad_dir.mkdir(parents=True)
        f = bad_dir / "fixture.py"
        f.write_text("import time\nT = time.time()\n")
        cwd = os.getcwd()
        os.chdir(tmp_path)
        try:
            rel = os.path.join("loro_tpu", "sync", "fixture.py")
            res = lint_paths([rel], baseline_path="")
            assert [x.rule for x in res.active] == ["LT-TIME"]
            bl = tmp_path / "baseline.json"
            from loro_tpu.analysis.core import baseline_payload

            bl.write_text(json.dumps(baseline_payload(res.active)))
            res2 = lint_paths([rel], baseline_path=str(bl))
            assert res2.active == [] and len(res2.baselined) == 1
        finally:
            os.chdir(cwd)

    def test_checked_in_baseline_is_empty(self):
        with open(DEFAULT_BASELINE) as f:
            assert json.load(f)["findings"] == []

    def test_foreign_checkout_paths_reanchor_for_scopes(self, tmp_path):
        """A file outside THIS repo root must still hit the rule
        scopes (re-anchored at its loro_tpu component) — a silent
        all-scopes-miss 'clean' on a foreign checkout would be worse
        than any finding."""
        pkg = tmp_path / "elsewhere" / "loro_tpu" / "sync"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text("import time\nT = time.time()\n")
        res = lint_paths([str(pkg / "bad.py")], baseline_path="")
        assert [f.rule for f in res.active] == ["LT-TIME"]


# ---------------------------------------------------------------------------
# the tier-1 repo gate + CLI
# ---------------------------------------------------------------------------


class TestRepoGate:
    def test_repo_is_lint_clean(self):
        """THE gate: zero unsuppressed findings over loro_tpu/ +
        chip_smoke.py, every suppression carrying a reason.
        A new finding means: fix it, or pragma it with the reason a
        reviewer should read."""
        res = lint_paths(
            [os.path.join(REPO, p) for p in ("loro_tpu", "chip_smoke.py")]
        )
        assert res.active == [], "\n" + "\n".join(
            f.render() for f in res.active
        )
        assert res.suppressed, "expected the documented catch-all pragmas"
        assert all(f.reason for f in res.suppressed)

    def test_analysis_metrics_ride_the_sidecar(self):
        from loro_tpu import obs

        lint_paths([os.path.join(REPO, "loro_tpu", "errors.py")])
        side = obs.sidecar()
        assert "analysis.suppressed_total" in side or \
            "analysis.findings_total" in side or side is not None
        # the suppression counter family exists after a repo lint
        lint_paths([os.path.join(REPO, "chip_smoke.py")])
        assert "analysis.suppressed_total" in obs.sidecar()

    def test_errors_rooted_in_loro_error(self):
        assert issubclass(AnalysisError, LoroError)
        assert issubclass(LockOrderViolation, AnalysisError)


class TestCli:
    def _run(self, args, cwd):
        return subprocess.run(
            [sys.executable, "-m", "loro_tpu.analysis.lint", *args],
            capture_output=True, text=True, cwd=cwd,
            env={**os.environ, "PYTHONPATH": REPO},
        )

    def test_cli_exit_codes_and_json(self, tmp_path):
        d = tmp_path / "loro_tpu" / "sync"
        d.mkdir(parents=True)
        (d / "fixture.py").write_text("import time\nT = time.time()\n")
        rel = os.path.join("loro_tpu", "sync", "fixture.py")
        r = self._run(["--baseline", "", rel], cwd=tmp_path)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "LT-TIME" in r.stdout
        j = self._run(["--baseline", "", "--format=json", rel], cwd=tmp_path)
        data = json.loads(j.stdout)
        assert data["ok"] is False
        assert data["counts"] == {"LT-TIME": 1}
        (d / "fixture.py").write_text("T = 0\n")
        r2 = self._run(["--baseline", "", rel], cwd=tmp_path)
        assert r2.returncode == 0, r2.stdout + r2.stderr

    def test_cli_list_rules(self, tmp_path):
        r = self._run(["--list-rules"], cwd=tmp_path)
        assert r.returncode == 0
        for rid in ("LT-DEV", "LT-PAD", "LT-HASH", "LT-TIME", "LT-EXC",
                    "LT-CHIP", "LT-LOCK"):
            assert rid in r.stdout


# ---------------------------------------------------------------------------
# lock witness
# ---------------------------------------------------------------------------


@pytest.fixture
def clean_witness():
    w = witness()
    was = w.enabled
    w.reset()
    yield w
    w.disable()
    w.reset()
    if was:
        w.enable()


class TestLockWitness:
    def test_deliberate_inversion_is_caught(self, clean_witness):
        w = clean_witness
        w.enable()
        dev = named_rlock("fleet.dev")
        route = named_rlock("sharded.route")
        with dev:
            with route:  # declared order says route is OUTSIDE dev
                pass
        assert w.check_declared(), "inverted acquisition must be flagged"
        assert ("fleet.dev", "sharded.route") in w.edges()

    def test_strict_mode_raises_at_the_acquire(self, clean_witness):
        w = clean_witness
        w.enable(strict=True)
        epoch = named_lock("sharded.epoch")
        queue = named_lock("pipeline.queue")
        with pytest.raises(LockOrderViolation, match="sharded.epoch"):
            with epoch:
                with queue:
                    pass

    def test_cycle_detection(self, clean_witness):
        w = clean_witness
        w.enable()
        a = named_lock("fixture.a")
        b = named_lock("fixture.b")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        # unknown names pass the declaration, but the cycle is a
        # latent deadlock regardless
        assert w.check_declared() == []
        with pytest.raises(LockOrderViolation, match="cycle"):
            w.assert_acyclic()

    def test_disable_mid_hold_does_not_leak_held_state(self, clean_witness):
        """Disabling the witness while a worker thread sits inside a
        critical section must not leave its lock name in the
        thread-local held-set: the release unwinds by RECORDED state,
        so a later enable() sees no phantom edges."""
        w = clean_witness
        w.enable()
        lk = named_rlock("fleet.dev")
        lk.acquire()
        w.disable()
        lk.release()
        w.enable()
        with named_lock("pipeline.queue"):
            pass
        assert w.edges() == {}

    def test_reentrant_same_name_is_not_an_edge(self, clean_witness):
        w = clean_witness
        w.enable()
        r1 = named_rlock("fleet.dev")
        with r1:
            with r1:  # reentrant
                pass
        r2 = named_rlock("fleet.dev")
        with r1:
            with r2:  # different instance, same name: sequential shards
                pass
        assert w.edges() == {}

    def test_condition_wait_keeps_bookkeeping(self, clean_witness):
        import threading

        w = clean_witness
        w.enable()
        lk = named_lock("fixture.cv")
        cv = threading.Condition(lk)
        hits = []

        def waiter():
            with cv:
                hits.append("in")
                cv.wait(timeout=5)
                hits.append("out")

        t = threading.Thread(target=waiter)
        t.start()
        while "in" not in hits:
            pass
        with cv:
            cv.notify_all()
        t.join(timeout=5)
        assert hits == ["in", "out"]
        # after the dance the waiter thread holds nothing
        assert w.edges() == {}

    def test_witness_runs_acyclic_across_the_real_planes(
        self, clean_witness, tmp_path
    ):
        """The acceptance path: pipelined resident ingest + sharded
        fleet (with a live migration) + sync sessions, witnessed; the
        graph must be non-empty, conformant to lockorder.LEVELS, and
        acyclic; the artifact dump round-trips."""
        from loro_tpu import LoroDoc
        from loro_tpu.doc import strip_envelope
        from loro_tpu.parallel.server import ResidentServer
        from loro_tpu.parallel.sharded import ShardedResidentServer
        from loro_tpu.sync import SyncServer

        w = clean_witness
        w.enable()

        def rounds_of(n, peer):
            d = LoroDoc(peer=peer)
            t = d.get_text("t")
            t.insert(0, "base")
            d.commit()
            mark = d.oplog_vv()
            out = [[strip_envelope(d.export_updates({}))]]
            for _ in range(n - 1):
                t.insert(0, "xyzw")
                d.commit()
                out.append([strip_envelope(d.export_updates(mark))])
                mark = d.oplog_vv()
            return d, out

        d, rounds = rounds_of(6, peer=31)
        cid = d.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        ex = srv.pipeline(cid=cid, coalesce=3, depth=2)
        prs = [ex.submit(list(r)) for r in rounds]
        ex.flush()
        assert [p.epoch() for p in prs]
        ex.close()
        srv.close()

        fleet = ShardedResidentServer("text", 4, shards=2, capacity=1 << 12)
        d2, rounds2 = rounds_of(4, peer=77)
        cid2 = d2.get_text("t").id
        pl = fleet.pipeline(cid=cid2, coalesce=2)
        for r in rounds2:
            pl.submit([r[0], None, None, None])
        pl.flush()
        pl.close()
        fleet.migrate(0, 1 - fleet.placement.place(0)[0])
        fleet.ingest([None, rounds2[0][0], None, None], cid2)
        fleet.close()

        ss = SyncServer("text", 2, cid=cid, capacity=1 << 12)
        c = ss.connect()
        dd = LoroDoc(peer=99)
        dd.get_text("t").insert(0, "hi")
        dd.commit()
        c.push(0, dd.export_updates({})).epoch()
        c.pull(0)
        c.set_presence({"name": "a"})
        ss.close()

        edges = w.edges()
        assert edges, "the planes must actually witness lock nesting"
        assert ("sharded.route", "sharded.collect") in edges
        assert w.check_declared() == [], w.check_declared()
        w.assert_acyclic()
        assert w.violations() == []

        art = w.dump(str(tmp_path / "lockwitness.json"))
        with open(art) as f:
            data = json.load(f)
        assert data["cycle"] is None and data["violations"] == []
        assert {(e["from"], e["to"]) for e in data["edges"]} == set(edges)
        assert data["levels"] == lockorder.LEVELS

    def test_declaration_is_internally_consistent(self):
        # every declared edge direction must be expressible: levels
        # unique, extra pairs not contradicting levels
        levels = list(lockorder.LEVELS.values())
        assert len(levels) == len(set(levels))
        for a, b in lockorder.ALLOWED_EXTRA:
            assert a in lockorder.LEVELS and b in lockorder.LEVELS


# ---------------------------------------------------------------------------
# satellite: injectable presence clocks (the LT-TIME burn-down)
# ---------------------------------------------------------------------------


class TestInjectableClocks:
    def test_awareness_ttl_under_fake_clock(self):
        from loro_tpu.awareness import Awareness

        now = [1000.0]
        a = Awareness(peer=1, timeout_s=30.0, clock=lambda: now[0])
        a.set_local_state({"x": 1})
        assert a.remove_outdated() == []
        now[0] += 31.0
        assert a.remove_outdated() == [1]
        assert a.get_all_states() == {}

    def test_ephemeral_ttl_under_fake_clock(self):
        from loro_tpu.awareness import EphemeralStore

        now = [50.0]
        s = EphemeralStore(timeout_ms=10_000, clock=lambda: now[0])
        s.set("k", "v")
        assert s.get("k") == "v"
        now[0] += 11.0
        assert s.remove_outdated() == ["k"]
        assert s.get("k") is None

    def test_presence_plane_threads_the_clock(self):
        from loro_tpu.sync.presence import PresencePlane

        class FakeServer:
            import threading as _t

            _lock = _t.RLock()
            _wakeup = _t.Condition(_lock)
            _sessions = {}
            family = "text"

        now = [7.0]
        p = PresencePlane(FakeServer(), ttl_s=5.0, clock=lambda: now[0])
        assert p.awareness.clock() == 7.0
        assert p.ephemeral.clock() == 7.0
