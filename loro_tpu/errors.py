"""Framework exception types (importable without doc.py's import graph)."""


class LoroError(Exception):
    pass


class DecodeError(LoroError):
    pass


class CodecDecodeError(DecodeError, ValueError):
    """Truncated / bit-flipped / otherwise malformed wire bytes.

    Subclasses ValueError on purpose: every ingest path that falls back
    to the Python decoder on `except ValueError` (fleet payload extract,
    resident append_payloads) keeps working unchanged, while callers
    that want the typed contract can catch CodecDecodeError (or
    DecodeError) specifically.
    """


class ConfigError(LoroError, ValueError):
    """Invalid configuration value (the LORO_NET_* and shard-count
    environment knobs, a chaos plan's fields, the sharded rank's
    ``algo``), raised at FIRST USE with the accepted values/range
    spelled out — never a silent fall-back to a default.

    Subclasses ValueError so pre-existing ``except ValueError`` guards
    (and tests) keep working.
    """

    def __init__(self, knob: str, got: object, accepted: str):
        self.knob = knob
        self.got = got
        self.accepted = accepted
        super().__init__(f"{knob}={got!r} invalid: accepted {accepted}")


class PersistError(LoroError):
    """Durability-layer failure (loro_tpu/persist/): a WAL append or
    checkpoint write did not reach disk, or a durable directory is in a
    state the requested operation cannot honor (e.g. opening an
    existing log as a fresh server).  Corrupt *reads* raise DecodeError
    subclasses instead — this type is for the write/lifecycle side."""


class SyncError(LoroError):
    """Base for the sync front-end (loro_tpu/sync/, docs/SYNC.md)."""


class PushRejected(SyncError):
    """A pushed update payload did not decode (poison): the push's
    ticket fails typed with this, other sessions' pushes in the same
    fan-in batch land normally.  The client should re-export and retry;
    the server state never half-applied the payload."""


class StaleFrontier(SyncError):
    """The client's frontier is below the server oracle's shallow root
    (history there was trimmed by the checkpoint ladder) AND the client
    is not empty, so neither a delta nor a snapshot can be served — the
    client must resync from scratch (fresh doc, then ``pull()`` takes
    the first-sync snapshot path)."""


class SessionClosed(SyncError):
    """Operation on a session that was closed or TTL-expired."""


class NetError(LoroError):
    """Base for the network edge (loro_tpu/net/, docs/NET.md): frame-
    layer violations (oversized frames, send-queue overflow, a closed
    or refused connection) and client-side transport failures.  A
    NetError fails exactly ONE connection — the accept loop and every
    other live session keep serving.  Truncated / bit-flipped frame
    *bytes* raise ``CodecDecodeError`` (the codec-harden contract);
    sync-layer outcomes crossing the wire re-raise their own types
    (``PushRejected``, ``StaleFrontier``, ``NotLeader``, ...)."""


class NetProtocolError(NetError):
    """The peer spoke the wrong protocol: bad HELLO magic, an
    unsupported protocol version, an unknown message type, or a frame
    whose declared length exceeds the negotiated maximum.  The
    connection closes typed; reconnect-with-frontier resume applies."""


class ShardingError(LoroError):
    """Sharded-fleet lifecycle misuse (loro_tpu/parallel/sharded.py,
    docs/SHARDING.md): migrating to a shard with no free slot, moving a
    doc on/off a degraded shard, a shard manifest that does not match
    the durable directories under it.  Invalid shard-count *knob*
    values (LORO_SHARDS, divisibility) raise ConfigError instead."""


class ResidencyError(LoroError):
    """Tiered-residency lifecycle failure (loro_tpu/parallel/residency.py,
    docs/RESIDENCY.md): a round touched more docs than the hot-slot
    budget can hold, no evictable victim exists (every hot doc is still
    un-journaled), or an injected/real failure interrupted an evict or
    revive.  The contract: a failed EVICT leaves the doc hot (no torn
    tier state); a failed REVIVE fails only the triggering round/ticket
    and leaves the doc warm/cold — the server itself stays healthy
    either way.  Passes through DeviceSupervisor untouched (LoroError),
    so it can never be misread as a device failure and trigger
    degradation."""


class ReplicationError(LoroError):
    """Base for WAL-shipping replication (loro_tpu/replication/,
    docs/REPLICATION.md): leader-side shipping, follower apply loops,
    fencing and promotion."""


class NotLeader(ReplicationError):
    """A write (push/ingest) reached a read-only follower.  Carries the
    current leader's identity so clients can redirect instead of
    guessing."""

    def __init__(self, msg: str, leader=None):
        self.leader = leader
        super().__init__(msg + (f" (leader: {leader})" if leader else ""))


class FencedLeader(ReplicationError):
    """A fenced (deposed) leader attempted a WAL append: a follower was
    promoted with a newer leader token, so this process must fail-stop
    — continuing to journal would fork the replicated history.  Raised
    BEFORE any bytes reach the segment (no partial record)."""


class StaleFollower(ReplicationError):
    """The follower's shipped position fell below the leader's WAL
    prune floor (its retention pin was dropped by the staleness
    cutoff, then the history it still needed was deleted).  The
    follower must re-bootstrap from a fresh directory — resuming would
    silently fabricate a truncated history."""


class ReplicaLag(ReplicationError):
    """A ``pull(min_epoch=...)`` read-your-writes gate timed out: the
    replica has not applied the requested epoch yet.  Retry, or pull
    from the leader."""


class ObsError(LoroError):
    """Observability-tooling failure (loro_tpu/obs/): an unreadable or
    malformed trace/flight artifact handed to ``python -m
    loro_tpu.obs.trace``, or a merge over artifacts with no common
    epoch stamps.  Always raised typed so the CLI exits with a legible
    message instead of a stack trace."""


class AnalysisError(LoroError):
    """Base for the static-analysis / invariant-witness subsystem
    (loro_tpu/analysis/, docs/ANALYSIS.md)."""


class LockOrderViolation(AnalysisError):
    """The runtime lock witness observed an acquisition the declared
    partial order in analysis/lockorder.py forbids, or a cycle in the
    witnessed lock graph (a latent deadlock).  Raised only in strict
    witness mode (tests) — production code never enables it."""


class ChaosError(LoroError):
    """Chaos-plane lifecycle misuse (loro_tpu/chaos/, docs/RESILIENCE.md
    "Chaos plane"): a malformed replay artifact, a plan step the runner
    does not understand, or orchestration misuse (resuming a run whose
    journal is missing).  Invalid chaos *knob* values raise ConfigError
    instead; invariant VIOLATIONS are never exceptions — they are data
    (``chaos.invariants.Violation``) so a run can report all of them."""


class ResilienceError(LoroError):
    """Base for the resilience subsystem (loro_tpu/resilience/)."""


class DeviceFailure(ResilienceError):
    """Supervisor-declared device failure: a launch raised a
    non-recoverable runtime error, or exhausted its retry budget on
    transient ``UNAVAILABLE``-class errors.  Callers degrade to the
    host ``models/`` engine or surface this typed error — never an
    untyped crash, never a hang."""

    def __init__(self, label: str, attempts: int = 1, cause: str = ""):
        self.label = label
        self.attempts = attempts
        super().__init__(
            f"device failure at {label!r} after {attempts} attempt(s)"
            + (f": {cause}" if cause else "")
        )


class DeadlineExceeded(ResilienceError):
    """A cooperative deadline expired BETWEEN launches.  Raised only at
    launch boundaries — never by interrupting a compile or a transfer
    (docs/RESILIENCE.md)."""
