"""Editing-trace sources for the chip smoke and the tests.

Analog of the reference's bench-utils crate (crates/bench-utils/src/
lib.rs:27-56 get_automerge_actions): an automerge-perf style linear
editing trace converted into the framework's op/element model.

Every run names its source (``TraceSource``), chosen by its caller.
The published ``automerge-paper.json.gz`` (259,778 single-character
patches) is not in this repository and nothing of the repository reads
outside its checkout, so the one source there is
``TraceSource.synthetic(seed, patches)``: a seeded trace of the same
shape (typing runs, ~10% deletes, positions valid at apply time), by
default at the published length.  A missing file never turns into a
shorter trace behind the caller's back.

Records carry ``source.record()`` so a number is never compared across
sources.  The extracted columnar tables are cached on disk (git-ignored,
keyed by the source) because the conversion — running the host engine
once to compute Fugue placements, the "source replica" role — is a
one-time cost per checkout.
"""
from __future__ import annotations

import os
import random
import zipfile
import zlib
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

# patches in the published automerge-paper trace (text_r.rs B4,
# BASELINE.md config 3): the default length of the synthetic source
PUBLISHED_PATCHES = 259_778
DEFAULT_SEED = 0xA07031

_ROOT = os.path.join(os.path.dirname(__file__), "..")

# Published peaks of the chips this repo has run on, keyed by
# ``jax.devices()[0].device_kind``.  A device that is not here is an
# error, not a default.  Source: Google Cloud documentation, "TPU v5e".
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},
}

# Extract-cache schema version.  Bump whenever the SeqExtract layout or
# the chain/run extraction semantics feeding it change: a cache written
# before such a change must be REBUILT, not mis-decoded (loads check the
# tag and fall through to regeneration on mismatch — including caches
# from before the tag existed).
CACHE_SCHEMA = 2

Patch = Tuple[int, int, str]


@dataclass(frozen=True)
class TraceSource:
    """Where a run's patches come from (see the module docstring)."""

    seed: int = DEFAULT_SEED
    patches: int = PUBLISHED_PATCHES  # trace length

    @classmethod
    def synthetic(cls, seed: int = DEFAULT_SEED,
                  patches: int = PUBLISHED_PATCHES) -> "TraceSource":
        return cls(int(seed), int(patches))

    def record(self) -> dict:
        """The fields a result record carries to name its source."""
        return {"trace": "synthetic", "seed": self.seed, "patches": self.patches}

    def tag(self) -> str:
        """Cache-file tag: distinct per source."""
        return f"syn{self.seed:x}_{self.patches}"

    def load(self, limit: Optional[int] = None) -> List[Patch]:
        """[(pos, del_len, insert_str)] single-char patches; ``limit``
        keeps a prefix."""
        return _synthetic_patches(self.seed, min(self.patches, limit or self.patches))


def _load_extract_cache(path: str):
    """SeqExtract + n_ops from an npz cache, or None when the cache is
    absent, carries a stale/missing schema tag, or is unreadable (a
    run killed mid-savez leaves a truncated zip — rebuild and
    overwrite instead of crashing every later run)."""
    from .ops.columnar import SeqExtract

    if not os.path.exists(path):
        return None
    try:
        z = np.load(path)
        if "schema" not in z.files or int(z["schema"]) != CACHE_SCHEMA:
            return None
        return SeqExtract(
            parent=z["parent"],
            side=z["side"],
            peer=z["peer"],
            counter=z["counter"],
            deleted=z["deleted"],
            content=z["content"],
            valid=z["valid"],
            peers=[int(p) for p in z["peers"]],
        ), int(z["n_ops"])
    except (OSError, EOFError, KeyError, ValueError, zipfile.BadZipFile,
            zlib.error):
        # stale/foreign/truncated cache file: rebuild instead of crashing
        return None


def _synthetic_patches(seed: int, n: int) -> List[Patch]:
    """Deterministic single-char editing trace with the automerge-perf
    shape (typing runs, ~10% deletes, positions valid at apply time).
    Everything downstream replays patches through the host engine, so
    variants, extraction and correctness gates work as on the real
    trace."""
    rng = random.Random(seed)
    patches: List[Patch] = []
    length = 0
    pos = 0
    run_left = 0
    while len(patches) < n:
        if run_left == 0:  # new editing burst at a fresh position
            pos = rng.randrange(length + 1)
            run_left = rng.randint(4, 24)
        run_left -= 1
        if length > 8 and rng.random() < 0.1:
            p = min(pos, length - 1)
            patches.append((p, 1, ""))
            length -= 1
            pos = min(p, length)
        else:
            p = min(pos, length)
            patches.append((p, 0, "etaoin shrdlu"[rng.randrange(13)]))
            length += 1
            pos = p + 1
    return patches


def automerge_seq_extract(source: TraceSource, limit: Optional[int] = None,
                          use_cache: bool = True):
    """SeqExtract of the whole trace (peer 1, linear history).
    Applies the trace through the host engine once to derive each op's
    Fugue (parent, side) placement, then explodes to columns."""
    from .doc import LoroDoc
    from .ops.columnar import extract_seq_container

    cache = None
    if use_cache and limit is None:
        cache = os.path.join(_ROOT, f".bench_cache_automerge_{source.tag()}.npz")
        hit = _load_extract_cache(cache)
        if hit is not None:
            return hit

    patches = source.load(limit)
    doc = LoroDoc(peer=1)
    t = doc.get_text("text")
    for pos, dels, ins in patches:
        if dels:
            t.delete(pos, dels)
        if ins:
            t.insert(pos, ins)
    doc.commit()
    changes = doc.oplog.changes_in_causal_order()
    ex = extract_seq_container(changes, t.id)
    n_ops = len(patches)
    if cache:
        np.savez_compressed(
            cache,
            parent=ex.parent,
            side=ex.side,
            peer=ex.peer,
            counter=ex.counter,
            deleted=ex.deleted,
            content=ex.content,
            valid=ex.valid,
            peers=np.asarray(ex.peers, np.uint64),
            n_ops=n_ops,
            schema=np.int64(CACHE_SCHEMA),
        )
    return ex, n_ops


def concurrent_trace_variant(patches: List[Patch], seed: int, v: int,
                             n_peers: int = 4, sync_every: int = 4000) -> dict:
    """One genuinely-concurrent multi-peer variant of a patch stream:
    the stream is routed across ``n_peers`` replicas in randomized
    windows (editing sessions interleave at window granularity — this
    preserves the trace's typing runs while creating real concurrency),
    all replicas syncing every ``sync_every`` patches and fully at the
    end.  The windows come from ``(seed, v)``; peer ids from ``v``.
    Host-only Python (no device, no JAX): a module-level function so
    that worker processes can run variants side by side.

    Returns a dict:
      payload: envelope-stripped update bytes (full history, all peers)
      extract: SeqExtract ((peer, counter)-sorted element table)
      text:    the converged document text (host-engine oracle)
      n_ops:   patches actually applied (clamped deletes drop)
    """
    from .doc import LoroDoc, strip_envelope
    from .ops.columnar import extract_seq_container

    rng = random.Random(seed * 1_000_003 + 0xBE5C + v)
    docs = [LoroDoc(peer=((v + 1) << 8) + i + 1) for i in range(n_peers)]
    texts = [d.get_text("text") for d in docs]

    def sync_all():
        for d in docs[1:]:
            docs[0].import_(d.export_updates(docs[0].oplog_vv()))
        for d in docs[1:]:
            d.import_(docs[0].export_updates(d.oplog_vv()))

    cur = 0
    window_left = 0
    n_applied = 0  # trace events actually applied (clamped deletes drop)
    for i, (pos, dels, ins) in enumerate(patches):
        if window_left == 0:
            cur = rng.randrange(n_peers)
            window_left = rng.randint(32, 256)
        window_left -= 1
        t = texts[cur]
        L = len(t)
        p = min(pos, L)
        applied = False
        if dels:
            d = min(dels, L - p)
            if d:
                t.delete(p, d)
                applied = True
        if ins:
            t.insert(p, ins)
            applied = True
        if applied:  # same unit as the pristine n_ops: patch events
            n_applied += 1
        if (i + 1) % sync_every == 0:
            sync_all()
    sync_all()
    sync_all()  # second round so every replica converges
    ref = docs[0]
    text = texts[0].to_string()
    for t in texts[1:]:
        if t.to_string() != text:
            raise RuntimeError(f"variant {v}: replicas failed to converge")
    payload = strip_envelope(ref.export_updates())
    ex = extract_seq_container(ref.oplog.changes_in_causal_order(), texts[0].id)
    return {"payload": payload, "extract": ex, "text": text, "n_ops": n_applied}
