"""The tpulint rule catalogue: the repo's survival rules as AST checks.

Every rule encodes one post-mortem or load-bearing invariant that used
to live only as prose (CLAUDE.md / docs/RESILIENCE.md).  Scopes are
path-shaped on purpose: a rule fires exactly where its invariant
applies, and the blessed-module lists below ARE the documentation of
where the device layer is allowed to live.  docs/ANALYSIS.md carries
the full catalogue with the story behind each rule.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from .core import Finding, ImportMap, ModuleSource, Rule, dotted, register
from .lockorder import STATIC_ATTR_LOCKS, allowed

# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

# the device layer: the only modules allowed to touch jax launch/fetch
# entry points directly.  Everything else must route through
# resilience.DeviceSupervisor (fleet's _sup_launch/_sup_fetch) so the
# drain budget, retry/backoff and typed DeviceFailure degradation hold
# on every path.
DEVICE_BLESSED = (
    "loro_tpu/ops/",
    "loro_tpu/parallel/fleet.py",
    "loro_tpu/parallel/mesh.py",
    "loro_tpu/resilience/",
)

# jax entry points that launch device work, allocate on device, or
# initialize the backend — the calls the supervisor exists to route.
# (jax.tree_util etc. are host-side and deliberately not listed.)
DEVICE_ENTRY_ATTRS = (
    "jit", "device_put", "device_get", "devices", "local_devices",
    "pallas_call", "pmap", "shard_map",
)


def _in(path: str, *prefixes: str) -> bool:
    return any(path.startswith(p) for p in prefixes)


def _pkg_only(path: str) -> bool:
    return path.startswith("loro_tpu/")


def _pkg_and_smoke(path: str) -> bool:
    return path.startswith("loro_tpu/") or path == "chip_smoke.py"


# ---------------------------------------------------------------------------
# LT-DEV — device calls outside the supervisor routing / blessed modules
# ---------------------------------------------------------------------------


@register(Rule(
    id="LT-DEV",
    name="unsupervised device call",
    summary="jax launch/fetch entry points outside DeviceSupervisor "
            "routing or the blessed kernel modules",
    post_mortem="every Fleet/resident device call routes through "
                "resilience.DeviceSupervisor (drain budget, retry, typed "
                "DeviceFailure) — a stray launch bypasses the drain budget "
                "and the degradation path (docs/RESILIENCE.md)",
    scope=lambda p: _pkg_only(p) and not _in(p, *DEVICE_BLESSED),
))
def check_device(mod: ModuleSource) -> Iterable[Finding]:
    imap = ImportMap(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        full = imap.resolve(node.func)
        if full is None or not full.startswith("jax"):
            continue
        if full.startswith("jax.numpy."):
            yield Finding(
                "LT-DEV", mod.path, node.lineno, node.col_offset + 1,
                f"{full.replace('jax.numpy', 'jnp')}() allocates/dispatches "
                "on device outside the blessed kernel modules — route the "
                "launch through resilience.DeviceSupervisor or move it into "
                "the device layer", source_line=mod.line(node.lineno),
            )
        elif full.split(".")[-1] in DEVICE_ENTRY_ATTRS:
            yield Finding(
                "LT-DEV", mod.path, node.lineno, node.col_offset + 1,
                f"{full}() is a device launch/backend entry point — only "
                "the blessed kernel modules call it directly; everything "
                "else goes through resilience.DeviceSupervisor "
                "(launch/guard/fetch)", source_line=mod.line(node.lineno),
            )


# ---------------------------------------------------------------------------
# LT-PAD — device-shape construction bypassing pad_bucket
# ---------------------------------------------------------------------------

_SHAPE_CTORS = ("zeros", "ones", "full", "empty")


def _has_raw_dynamic_dim(node: ast.AST) -> bool:
    """True when the (shape) expression contains a len(...) call or a
    ``.shape[...]`` subscript that is NOT wrapped in pad_bucket(...).
    Variables are invisible to this check on purpose — the lint flags
    the inline smoking gun, not every possible data flow."""
    # ancestor-aware walk: flag len()/.shape[...] nodes with no
    # pad_bucket call between them and the root
    stack = [(node, False)]
    while stack:
        cur, padded = stack.pop()
        if isinstance(cur, ast.Call):
            f = dotted(cur.func)
            if f == "pad_bucket" or (f or "").endswith(".pad_bucket"):
                padded = True
            elif not padded and isinstance(cur.func, ast.Name) \
                    and cur.func.id == "len":
                return True
        if not padded and isinstance(cur, ast.Subscript):
            if isinstance(cur.value, ast.Attribute) \
                    and cur.value.attr == "shape":
                return True
        for child in ast.iter_child_nodes(cur):
            stack.append((child, padded))
    return False


@register(Rule(
    id="LT-PAD",
    name="unbucketed device shape",
    summary="device-array construction (jnp.*, or np.* inline in a "
            "device_put) in fleet/serving paths from a raw len()/.shape[] "
            "size instead of pad_bucket",
    post_mortem="every distinct padded shape is a fresh jit compile — "
                "unbucketed DEVICE shapes explode the jit cache (the "
                "CLAUDE.md invariant; obs tracks cardinality as "
                "fleet.padded_shapes).  Host staging buffers are exempt: "
                "the invariant bites at the device boundary, where the "
                "existing paths all pad_bucket before device_put",
    scope=lambda p: _in(p, "loro_tpu/parallel/", "loro_tpu/ops/"),
))
def check_pad(mod: ModuleSource) -> Iterable[Finding]:
    imap = ImportMap(mod.tree)

    def ctor_path(call: ast.Call) -> str:
        full = imap.resolve(call.func) or ""
        return full if full.split(".")[-1] in _SHAPE_CTORS else ""

    def flag(call: ast.Call, full: str, where: str):
        return Finding(
            "LT-PAD", mod.path, call.lineno, call.col_offset + 1,
            f"{full.split('.')[-1]}() {where} shapes from a raw dynamic "
            "size (len()/.shape[]) — bucket it through pad_bucket() or "
            "the jit cache grows one entry per distinct size",
            source_line=mod.line(call.lineno),
        )

    inline_device = set()  # np-ctor calls inside a device_put argument
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and \
                (imap.resolve(node.func) or "").endswith("device_put"):
            for arg in node.args[:1]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Call) and ctor_path(sub):
                        inline_device.add(id(sub))
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        full = ctor_path(node)
        if not full:
            continue
        if full.startswith("jax.numpy."):
            if _has_raw_dynamic_dim(node.args[0]):
                yield flag(node, full, "allocates on device and")
        elif id(node) in inline_device and _has_raw_dynamic_dim(node.args[0]):
            yield flag(node, full, "feeds device_put and")


# ---------------------------------------------------------------------------
# LT-HASH — builtin hash()/unseeded randomness in placement/wire paths
# ---------------------------------------------------------------------------

_RANDOM_FNS = (
    "random", "getrandbits", "randint", "randrange", "choice", "choices",
    "shuffle", "sample", "uniform", "random.seed",
)


@register(Rule(
    id="LT-HASH",
    name="non-deterministic hash/randomness",
    summary="builtin hash() or module-level random.* in placement, "
            "journaling or wire paths that require keyed blake2b / "
            "seeded RNGs",
    post_mortem="builtin hash() is salted per process (PYTHONHASHSEED): "
                "rendezvous placement, WAL framing or wire layouts keyed "
                "on it silently disagree across processes — placement uses "
                "keyed blake2b for exactly this (parallel/placement.py)",
    scope=lambda p: _in(
        p, "loro_tpu/parallel/placement.py", "loro_tpu/parallel/sharded.py",
        "loro_tpu/persist/", "loro_tpu/codec/", "loro_tpu/storage/",
        "loro_tpu/sync/", "loro_tpu/oplog/",
    ),
))
def check_hash(mod: ModuleSource) -> Iterable[Finding]:
    imap = ImportMap(mod.tree)
    # hash() inside __hash__ implementations is the language protocol,
    # not a placement decision
    hash_ok_ranges: List[range] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef) and node.name == "__hash__":
            hash_ok_ranges.append(range(node.lineno, (node.end_lineno or
                                                      node.lineno) + 1))
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name) and node.func.id == "hash":
            if any(node.lineno in r for r in hash_ok_ranges):
                continue
            yield Finding(
                "LT-HASH", mod.path, node.lineno, node.col_offset + 1,
                "builtin hash() is process-salted — use keyed blake2b "
                "(parallel/placement.py idiom) for anything that must "
                "agree across runs/processes",
                source_line=mod.line(node.lineno),
            )
            continue
        full = imap.resolve(node.func) or ""
        if full.startswith("random.") and full != "random.Random" \
                and full.split(".")[-1] in _RANDOM_FNS:
            yield Finding(
                "LT-HASH", mod.path, node.lineno, node.col_offset + 1,
                f"{full}() draws from the process-global unseeded RNG — "
                "placement/journal/wire paths need deterministic bytes "
                "(keyed blake2b or an explicit random.Random(seed))",
                source_line=mod.line(node.lineno),
            )


# ---------------------------------------------------------------------------
# LT-TIME — wall clock in logic the fake-clock tests must control
# ---------------------------------------------------------------------------


@register(Rule(
    id="LT-TIME",
    name="uninjected wall clock",
    summary="time.time() in epoch/retry/TTL logic that must use the "
            "injected clock the fake-clock tests rely on",
    post_mortem="tier-1 never wall-sleeps: supervisor retry/backoff and "
                "TTL expiry run under injected clocks (DeviceSupervisor"
                "(clock=, sleep=)) — a raw time.time() site is untestable "
                "without real sleeps and drifts vs the fake clock",
    scope=_pkg_only,
))
def check_time(mod: ModuleSource) -> Iterable[Finding]:
    imap = ImportMap(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if (imap.resolve(node.func) or "") == "time.time":
            yield Finding(
                "LT-TIME", mod.path, node.lineno, node.col_offset + 1,
                "time.time() called directly — take an injectable "
                "clock (clock=time.time parameter, the DeviceSupervisor "
                "idiom) so fake-clock tests control it",
                source_line=mod.line(node.lineno),
            )


# ---------------------------------------------------------------------------
# LT-EXC — broad catches that swallow, and untyped error classes
# ---------------------------------------------------------------------------

_BUILTIN_EXC_BASES = {
    "Exception", "BaseException", "ValueError", "TypeError", "RuntimeError",
    "KeyError", "IndexError", "OSError", "IOError", "ArithmeticError",
}
_ERRORISH = ("Error", "Failure", "Rejected", "Exceeded", "Closed")


def _handler_swallows(h: ast.ExceptHandler) -> bool:
    """True when the handler body contains no raise: the error is
    swallowed rather than re-raised typed."""
    for node in ast.walk(h):
        if isinstance(node, ast.Raise):
            return False
    return True


def _catches_broad(h: ast.ExceptHandler) -> bool:
    if h.type is None:
        return True  # bare except:
    types = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
    for t in types:
        if isinstance(t, ast.Name) and t.id == "Exception":
            return True
    return False


@register(Rule(
    id="LT-EXC",
    name="untyped exception discipline",
    summary="except Exception that swallows (no raise in the handler) "
            "where the typed hierarchy in errors.py applies; error "
            "classes not rooted in LoroError",
    post_mortem="typed errors are the degradation contract: "
                "DeviceFailure -> host fallback, CodecDecodeError -> "
                "poison isolation, PushRejected -> per-ticket failure.  A "
                "silent broad catch eats the signal those paths key on",
    scope=_pkg_and_smoke,
))
def check_exc(mod: ModuleSource) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ExceptHandler):
            if _catches_broad(node) and _handler_swallows(node):
                what = "bare except:" if node.type is None \
                    else "except Exception"
                yield Finding(
                    "LT-EXC", mod.path, node.lineno, node.col_offset + 1,
                    f"{what} swallows the error (no raise in the handler) "
                    "— catch the typed errors.py class that applies, or "
                    "pragma the genuine catch-all with its reason",
                    source_line=mod.line(node.lineno),
                )
        elif isinstance(node, ast.ClassDef) and mod.path != "loro_tpu/errors.py":
            if not node.name.endswith(_ERRORISH) or not node.bases:
                continue
            base_names = [dotted(b) or "" for b in node.bases]
            exceptionish = any(
                b.split(".")[-1] in _BUILTIN_EXC_BASES for b in base_names
            )
            typed = any(
                b.split(".")[-1] not in _BUILTIN_EXC_BASES and b
                for b in base_names
            )
            if exceptionish and not typed:
                yield Finding(
                    "LT-EXC", mod.path, node.lineno, node.col_offset + 1,
                    f"error class {node.name} subclasses only builtin "
                    "exceptions — root it in the errors.py hierarchy "
                    "(LoroError) so typed catches and the degradation "
                    "contract see it", source_line=mod.line(node.lineno),
                )


# ---------------------------------------------------------------------------
# LT-CHIP — never signal the process that holds the chip
# ---------------------------------------------------------------------------


@register(Rule(
    id="LT-CHIP",
    name="signal at the chip's owner",
    summary="os.kill / .terminate() / .kill() / .send_signal() at a "
            "process that may hold the chip",
    post_mortem="a chip belongs to one process at a time; a signal that "
                "lands while its owner is mid-compile or mid-transfer can "
                "leave the device unusable for whatever runs next — stop "
                "such a process cooperatively (a deadline it checks "
                "between launches, a stop file) and signal only processes "
                "that are host-only (pinned to the CPU), saying so in the "
                "pragma",
    scope=_pkg_and_smoke,
))
def check_chip(mod: ModuleSource) -> Iterable[Finding]:
    imap = ImportMap(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if (imap.resolve(node.func) or "") == "os.kill":
            sig = node.args[1] if len(node.args) > 1 else None
            if isinstance(sig, ast.Constant) and sig.value == 0:
                continue  # signal 0 = existence probe, sends nothing
            yield Finding(
                "LT-CHIP", mod.path, node.lineno, node.col_offset + 1,
                "os.kill at a process that may hold the chip — size runs "
                "to finish and stop them cooperatively; never signal "
                "mid-compile/mid-transfer",
                source_line=mod.line(node.lineno),
            )
            continue
        if isinstance(node.func, ast.Attribute) and (
            node.func.attr == "send_signal"
            or (node.func.attr in ("terminate", "kill") and not node.args
                and not node.keywords)
        ):
            yield Finding(
                "LT-CHIP", mod.path, node.lineno, node.col_offset + 1,
                f".{node.func.attr}() on a child that may hold the chip — "
                "stop it cooperatively, or say in the pragma why it is "
                "host-only",
                source_line=mod.line(node.lineno),
            )


# ---------------------------------------------------------------------------
# LT-LOCK — static companion of the runtime lock witness
# ---------------------------------------------------------------------------


@register(Rule(
    id="LT-LOCK",
    name="declared-lock-order inversion",
    summary="a with-acquisition of a known named lock while a lock the "
            "declared order places BELOW it is already held",
    post_mortem="the fleet's thread planes (pipeline stage/commit, "
                "sharded fan-out/collector, fan-in, supervisors) share a "
                "declared partial lock order (analysis/lockorder.py); an "
                "inverted static acquisition is a latent deadlock the "
                "runtime witness would only catch when the schedule hits it",
    scope=lambda p: _in(p, "loro_tpu/parallel/", "loro_tpu/sync/",
                        "loro_tpu/resilience/"),
))
def check_lock(mod: ModuleSource) -> Iterable[Finding]:
    def lock_name(expr: ast.AST):
        d = dotted(expr)
        if d is None:
            return None
        return STATIC_ATTR_LOCKS.get(d.split(".")[-1])

    def walk(node: ast.AST, held: List[str]):
        if isinstance(node, ast.With):
            acquired: List[str] = []
            for item in node.items:
                nm = lock_name(item.context_expr)
                if nm is None:
                    continue
                for h in held + acquired:
                    if h != nm and not allowed(h, nm):
                        yield Finding(
                            "LT-LOCK", mod.path, item.context_expr.lineno,
                            item.context_expr.col_offset + 1,
                            f"acquires {nm!r} while holding {h!r} — the "
                            "declared order (analysis/lockorder.py) puts "
                            f"{nm!r} outside {h!r}; invert the nesting or "
                            "amend the declaration with its justification",
                            source_line=mod.line(item.context_expr.lineno),
                        )
                acquired.append(nm)
            for child in node.body:
                yield from walk(child, held + acquired)
            return
        # function boundaries reset held-set (a called function's own
        # with-blocks are analyzed in its own frame)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for child in node.body:
                yield from walk(child, [])
            return
        for child in ast.iter_child_nodes(node):
            yield from walk(child, held)

    yield from walk(mod.tree, [])
