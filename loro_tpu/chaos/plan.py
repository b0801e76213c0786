"""Deterministic chaos plans: seeded fault/nemesis schedules.

A **ChaosPlan** is a flat list of steps drawn from ONE ``random.Random``
seeded with the config's seed — the plan (and therefore the whole run,
executed by the single-threaded ``chaos.runner``) is a pure function of
``(config, seed)``.  Step kinds:

- ``edit``      — one client edits every container family in its doc
                  and pushes the delta to every family server (the
                  soak_sync write pattern); carries its own derived
                  ``seed`` so the edit bytes are reproducible from the
                  step record alone
- ``pull``      — one client pulls (byte-identity-gated vs the serving
                  oracle's own export)
- ``fault``     — arm one entry of the SAFE arm matrix below through
                  the programmatic ``resilience.faultinject`` API
- ``join`` / ``leave`` / ``stall`` — session churn (a stalled client
                  skips pulls until the barrier after next clears it)
- ``checkpoint`` / ``compact`` — durability/retention housekeeping on
                  one family
- ``demote``    — push a warm doc to the cold tier (tiered servers)
- ``migrate``   — live-migrate one doc to the next shard
- ``net``       — socket-edge nemesis: front one family's SyncServer
                  with a ``net.NetServer``, pull over a REAL TCP
                  socket byte-identity-gated against the oracle's own
                  export, inject a seeded connection fault (writer
                  stall / frame bitflip / accept refusal), kill the
                  connection abruptly and reconnect-with-frontier —
                  the resumed pull is gated the same way.  Read-only
                  by construction: pushes stay on the in-process
                  sessions, so the reference oracle's acked-payload
                  bookkeeping is untouched
- ``reopen``    — graceful close + ``recover_sharded_server`` +
                  re-front + follower resume + client reset (the
                  in-process recovery nemesis)
- ``promote``   — failover: retire the leader, promote its follower,
                  reconnect everything (at most one per plan, late)
- ``kill``      — SIGKILL point: an orchestrating parent (soak_chaos /
                  ``chaos.run --hold-at``) kills the child here and
                  resumes from the durable dirs; executed in-process it
                  downgrades to ``reopen`` on every family (counted)
- ``check``     — invariant barrier (``chaos.invariants``)
- ``plant``     — test-only synthetic violation: corrupts the
                  REFERENCE oracle so the next barrier must catch it
                  (generated only when ``plant_at`` is set — the hook
                  the determinism/replay/shrink acceptance tests use)

**Safe arm matrix.**  Only fault arms whose documented degradation
contract preserves end-to-end convergence under a live SyncServer are
generated; the rest of the registry stays covered by targeted tests.
Excluded, with reasons: ``poison_doc`` (mangles bytes BELOW the sync
fan-in — the serving oracle has already accepted the push, so resident
reads diverge by design), ``decode`` under payload routing is included
(the native wrapper falls back to the Python decoder with the ORIGINAL
bytes), ``wal_write:raise`` (documented fail-stop — the server is DOWN
afterwards, which is a crash test, not a composition test),
``wal_torn_tail``/``ckpt_corrupt`` (byzantine-disk mangling: the
durable bytes no longer match what the server acked, which the
convergence oracle cannot model — targeted recovery tests own them),
``evict_flush`` (armed only
PAIRED directly before a ``demote`` step: fired mid-sync-ingest it
would fail the fan-in worker, a known contract documented in
docs/RESILIENCE.md), ``revive_replay`` (same pairing problem without a
pairable runner-side trigger — a revive fires inside the fan-in commit
path, where a typed per-round failure still closes the intake).
"""
from __future__ import annotations

import json
import random
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

from ..errors import ChaosError, ConfigError

ALL_FAMILIES = ("text", "map", "tree", "counter", "movable")

#: fault arms the generator may compose mid-run (site, kwargs).  Every
#: entry is convergence-safe: it either retries clean, degrades to a
#: byte-identical host path, or fails typed to the runner which retries
#: the client operation with the fault exhausted.
SAFE_ARMS: Tuple[dict, ...] = (
    {"site": "launch", "action": "raise", "times": 1},            # transient
    {"site": "launch", "action": "raise", "times": 1,
     "msg": "injected fatal launch"},                             # degrade
    {"site": "fetch", "action": "delay", "delay_s": 0.005},
    {"site": "decode", "action": "truncate", "times": 1},
    {"site": "decode", "action": "bitflip", "times": 1},
    {"site": "wal_write", "action": "delay", "delay_s": 0.005},
    {"site": "sync_push", "action": "raise", "times": 1},
    {"site": "sync_push", "action": "bitflip", "times": 1},
    {"site": "sync_pull", "action": "raise", "times": 1},
    {"site": "sync_pull", "action": "delay", "delay_s": 0.005},
    {"site": "session_stall", "action": "delay", "delay_s": 0.005},
    {"site": "read_batch", "action": "raise", "times": 1},
    {"site": "export_launch", "action": "raise", "times": 1},
    {"site": "export_launch", "action": "raise", "times": 1,
     "msg": "injected fatal export"},
    {"site": "health_tick", "action": "raise", "times": 1},
)

#: arms that only make sense when a follower is riding along
REPL_ARMS: Tuple[dict, ...] = (
    {"site": "repl_ship", "action": "raise", "times": 1},
    {"site": "repl_ship", "action": "delay", "delay_s": 0.005},
    {"site": "repl_ship", "action": "truncate", "times": 1},
    {"site": "repl_apply", "action": "raise", "times": 1},
)


@dataclass(frozen=True)
class Step:
    """One schedulable action.  ``params`` must stay JSON-able — the
    step trace IS the replay/shrink artifact."""

    i: int
    kind: str
    params: Dict[str, object] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"i": self.i, "kind": self.kind, "params": dict(self.params)}

    @classmethod
    def from_json(cls, d: dict) -> "Step":
        try:
            return cls(i=int(d["i"]), kind=str(d["kind"]),
                       params=dict(d.get("params", {})))
        except (KeyError, TypeError, ValueError) as e:
            raise ChaosError(f"malformed step record {d!r}: {e}") from e


@dataclass
class ChaosConfig:
    """Plan/run parameters.  ``seed`` + this config fully determine the
    plan; the runner adds no randomness of its own."""

    seed: int = 0
    steps: int = 40
    families: Tuple[str, ...] = ALL_FAMILIES
    docs: int = 4
    shards: int = 2
    hot_slots: Optional[int] = 2
    sessions: int = 3
    fsync_window: int = 4
    barrier_every: int = 10
    coalesce: int = 4
    follower: bool = True
    allow_kill: bool = False
    plant_at: Optional[int] = None   # test-only synthetic violation

    def __post_init__(self):
        self.families = tuple(self.families)
        bad = [f for f in self.families if f not in ALL_FAMILIES]
        if bad or not self.families:
            raise ConfigError(
                "chaos families", ",".join(bad) or "(empty)",
                "non-empty subset of " + ",".join(ALL_FAMILIES),
            )
        for knob, v, lo in (("steps", self.steps, 1),
                            ("docs", self.docs, 1),
                            ("shards", self.shards, 1),
                            ("sessions", self.sessions, 1),
                            ("barrier_every", self.barrier_every, 1)):
            if int(v) < lo:
                raise ConfigError(f"chaos {knob}", v, f"integer >= {lo}")

    def to_json(self) -> dict:
        d = asdict(self)
        d["families"] = list(self.families)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ChaosConfig":
        try:
            d = dict(d)
            d["families"] = tuple(d.get("families", ALL_FAMILIES))
            return cls(**d)
        except TypeError as e:
            raise ChaosError(f"malformed chaos config: {e}") from e


def _weighted(rng: random.Random, table: List[Tuple[str, float]]) -> str:
    total = sum(w for _, w in table)
    x = rng.random() * total
    for kind, w in table:
        x -= w
        if x < 0:
            return kind
    return table[-1][0]


def generate_plan(cfg: ChaosConfig) -> List[Step]:
    """The seeded schedule: every draw comes from one PRNG, so two
    calls with equal configs produce byte-identical step traces."""
    rng = random.Random(cfg.seed)
    arms = list(SAFE_ARMS) + (list(REPL_ARMS) if cfg.follower else [])
    table: List[Tuple[str, float]] = [
        ("edit", 8.0), ("pull", 3.0), ("fault", 3.0), ("join", 0.7),
        ("leave", 0.7), ("stall", 1.0), ("checkpoint", 1.0),
        ("compact", 0.7), ("net", 0.6),
    ]
    if cfg.hot_slots is not None:
        table.append(("demote", 1.5))
    if cfg.shards > 1:
        table.append(("migrate", 1.0))
    table.append(("reopen", 0.4))
    # at most one promote, drawn up front so its position is stable
    promote_at = None
    if cfg.follower and cfg.steps >= 8 and rng.random() < 0.5:
        promote_at = rng.randrange(3 * cfg.steps // 4, cfg.steps)
    kill_ats: set = set()
    if cfg.allow_kill:
        for _ in range(max(1, cfg.steps // 25)):
            kill_ats.add(rng.randrange(cfg.steps // 4, cfg.steps))

    raw: List[Step] = []

    def emit(kind: str, **params) -> None:
        raw.append(Step(i=len(raw), kind=kind, params=params))

    for n in range(cfg.steps):
        if cfg.plant_at is not None and n == cfg.plant_at:
            emit("plant", seed=rng.randrange(1 << 30))
        if n == promote_at:
            if rng.random() < 0.4:
                emit("fault", site="repl_promote", action="raise", times=1)
            emit("promote", family=rng.choice(cfg.families))
        elif n in kill_ats:
            emit("kill")
        else:
            kind = _weighted(rng, table)
            if kind == "edit":
                emit("edit", client=rng.randrange(1 << 30),
                     seed=rng.randrange(1 << 30), ops=rng.randint(2, 5))
            elif kind == "pull":
                emit("pull", client=rng.randrange(1 << 30))
            elif kind == "fault":
                emit("fault", **rng.choice(arms))
            elif kind == "join":
                emit("join", doc=rng.randrange(cfg.docs))
            elif kind == "leave":
                emit("leave", client=rng.randrange(1 << 30))
            elif kind == "stall":
                emit("stall", client=rng.randrange(1 << 30))
            elif kind == "checkpoint":
                emit("checkpoint", family=rng.choice(cfg.families))
            elif kind == "compact":
                emit("compact", family=rng.choice(cfg.families))
            elif kind == "net":
                emit("net", family=rng.choice(cfg.families),
                     seed=rng.randrange(1 << 30))
            elif kind == "demote":
                emit("demote", family=rng.choice(cfg.families),
                     pick=rng.randrange(1 << 30))
            elif kind == "migrate":
                emit("migrate", family=rng.choice(cfg.families),
                     doc=rng.randrange(cfg.docs))
            elif kind == "reopen":
                emit("reopen", family=rng.choice(cfg.families))
        if (n + 1) % cfg.barrier_every == 0:
            # a fault armed since the last barrier may sit unfired; the
            # barrier's settle phase clears it (counted) so checks run
            # against a quiesced stack
            emit("check")
    if not raw or raw[-1].kind != "check":
        emit("check")
    return raw


def trace_json(steps: List[Step]) -> str:
    """Canonical serialized step trace (the determinism gate compares
    these byte-for-byte)."""
    return json.dumps([s.to_json() for s in steps],
                      sort_keys=True, separators=(",", ":"))


def steps_from_json(rows: List[dict]) -> List[Step]:
    return [Step.from_json(r) for r in rows]
