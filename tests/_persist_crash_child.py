"""Shared driver for the persist crash-recovery test (NOT collected —
no test_ prefix).

As a script (the subprocess the test SIGKILLs)::

    python tests/_persist_crash_child.py <base_dir> <rounds> <ckpt_at> \
        [fsync_mode] [fsync_window]

drives all five resident families through ``rounds`` deterministic
ingest rounds against durable servers under ``<base_dir>/<family>``,
checkpoints at round ``ckpt_at``, writes ``<base_dir>/READY`` and then
sleeps — the parent kills it there, BETWEEN launches (this is a
CPU-mesh process: the test never signals a process that holds a
chip, docs/RESILIENCE.md rule 2).

``fsync_mode="group"`` runs the servers in WAL group-commit mode with
the given window, and appends one line per round to
``<base_dir>/<family>.progress`` (``round epoch durable_epoch``,
flushed to the OS) — the parent's oracle for the acked-epoch
watermark the crash must not lose.

As a module (imported by the parent test): ``make_doc``/``apply_edit``
regenerate the byte-identical edit stream for the host oracle, and
``read_server``/``read_oracle`` produce comparable views.
"""
import os
import os.path as _p
import sys

sys.path.insert(0, _p.dirname(_p.dirname(_p.abspath(__file__))))  # repo root

FAMILIES = ["text", "map", "tree", "movable", "counter"]

CAPS = {
    "text": dict(capacity=1 << 12),
    "map": dict(slot_capacity=128),
    "tree": dict(move_capacity=1 << 10, node_capacity=256),
    "movable": dict(capacity=1 << 10, elem_capacity=256),
    "counter": dict(slot_capacity=32),
}

_PEER = {f: 9000 + i for i, f in enumerate(FAMILIES)}


def make_doc(family, idx=0):
    from loro_tpu import LoroDoc

    d = LoroDoc(peer=_PEER[family] + 100 * idx)
    if family == "text":
        d.get_text("t").insert(0, "crash base text")
    elif family == "map":
        d.get_map("m").set("k0", 0)
    elif family == "tree":
        d.get_tree("tr").create()
    elif family == "movable":
        d.get_movable_list("ml").push("a", "b", "c")
    elif family == "counter":
        d.get_counter("c").increment(1)
    d.commit()
    return d


def apply_edit(d, family, r):
    """Deterministic round-``r`` edit (same bytes in child and
    oracle)."""
    if family == "text":
        t = d.get_text("t")
        t.insert(min(r, len(t)), f"r{r} ")
        if r % 2 == 0:
            t.mark(0, 3, "bold", True if r % 4 == 0 else None)
        if r % 3 == 0 and len(t) > 6:
            t.delete(1, 2)
    elif family == "map":
        m = d.get_map("m")
        m.set(f"k{r % 3}", r * 10)
        if r % 4 == 0:
            m.delete("k1")
    elif family == "tree":
        tr = d.get_tree("tr")
        nodes = tr.nodes()
        n = tr.create(nodes[r % len(nodes)] if r % 2 == 0 and nodes else None)
        nodes = tr.nodes()
        if r % 3 == 0 and len(nodes) >= 2:
            tr.move(nodes[-1], nodes[0])
    elif family == "movable":
        ml = d.get_movable_list("ml")
        L = len(ml.get_value())
        ml.insert(r % (L + 1), f"v{r}")
        L += 1
        if r % 2 == 0 and L >= 2:
            ml.move(r % L, (r * 2) % L)
        if r % 3 == 0:
            ml.set(r % L, f"w{r}")
    elif family == "counter":
        d.get_counter("c").increment(r * 3 - 5)
    d.commit()


def container_id(family, d):
    if family == "text":
        return d.get_text("t").id
    if family == "tree":
        return d.get_tree("tr").id
    if family == "movable":
        return d.get_movable_list("ml").id
    return None


def read_server(srv, family):
    if family == "text":
        return (srv.texts()[0], srv.richtexts()[0])
    if family == "map":
        return srv.root_value_maps("m")[0]
    if family == "tree":
        return (srv.parent_maps()[0], srv.children_maps()[0])
    if family == "movable":
        return srv.value_lists()[0]
    return srv.value_maps()[0]


def read_oracle(d, family):
    if family == "text":
        t = d.get_text("t")
        return (t.to_string(), t.get_richtext_value())
    if family == "map":
        return d.get_map("m").get_value()
    if family == "tree":
        tr = d.get_tree("tr")
        kids = {}
        for x in [None] + tr.nodes():
            ch = tr.children(x)
            if ch:
                kids[x] = ch
        return ({x: tr.parent(x) for x in tr.nodes()}, kids)
    if family == "movable":
        return d.get_movable_list("ml").get_value()
    c = d.get_counter("c")
    return {c.id: float(c.get_value())}


TIERED_DOCS = 3  # CRASH_TIERED mode: docs per family, hot_slots=1


def tiered_doc_of_round(r: int) -> int:
    """Which doc round ``r`` touches in CRASH_TIERED mode (rotating —
    every round is a miss at hot_slots=1, maximal evict/revive churn).
    Shared with the parent test's oracle."""
    return (r - 1) % TIERED_DOCS


def main(base_dir, rounds, ckpt_at, fsync_mode="per_round", fsync_window=0):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from loro_tpu.parallel.server import ResidentServer

    group = fsync_mode == "group"
    tiered = os.environ.get("CRASH_TIERED", "0") == "1"
    n_docs = TIERED_DOCS if tiered else 1
    kw = {}
    if group:
        kw = dict(durable_fsync="group",
                  fsync_window=fsync_window or 4)
    if tiered:
        # SIGKILL-during-evict/revive-churn coverage (docs/RESIDENCY.md):
        # 3 docs over 1 hot slot, every round revives a warm/cold doc
        kw["hot_slots"] = 1
    servers, docs, marks = {}, {}, {}
    for fam in FAMILIES:
        docs[fam] = [make_doc(fam, i) for i in range(n_docs)]
        servers[fam] = ResidentServer(
            fam, n_docs, durable_dir=os.path.join(base_dir, fam),
            **CAPS[fam], **kw,
        )
        marks[fam] = [None] * n_docs
    for r in range(1, rounds + 1):
        for fam in FAMILIES:
            srv = servers[fam]
            di = tiered_doc_of_round(r) if tiered else 0
            d = docs[fam][di]
            if marks[fam][di] is None:
                chs = d.oplog.changes_in_causal_order()
            else:
                apply_edit(d, fam, r)
                chs = d.oplog.changes_between(marks[fam][di], d.oplog_vv())
            marks[fam][di] = d.oplog_vv()
            ups = [None] * n_docs
            ups[di] = chs
            srv.ingest(ups, container_id(fam, d))
            if r == ckpt_at:
                srv.checkpoint()
                if tiered:
                    # push one warm doc to the cold tier so the crash
                    # window covers a rung-backed doc too
                    warm = srv.residency.tiers()["warm"]
                    if warm:
                        srv.batch.demote(warm[0])
            if group:
                # one flushed line per round: the parent's watermark
                # oracle (flush() reaches the OS, which survives the
                # SIGKILL; only power loss would need an fsync here)
                with open(os.path.join(base_dir, fam + ".progress"), "a") as f:
                    f.write(f"{r} {srv.epoch} {srv.durable_epoch}\n")
                    f.flush()
    with open(os.path.join(base_dir, "READY"), "w") as f:
        f.write("ready")
    import time

    time.sleep(300.0)  # the parent SIGKILLs us here, between launches


if __name__ == "__main__":
    main(
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
        sys.argv[4] if len(sys.argv) > 4 else "per_round",
        int(sys.argv[5]) if len(sys.argv) > 5 else 0,
    )
