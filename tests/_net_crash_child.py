"""Driver child for the net SIGKILL-reconnect test (NOT collected —
no test_ prefix).

As a script (the subprocess the test SIGKILLs)::

    python tests/_net_crash_child.py <host> <port> <family> <dir> \
        <rounds> <seed>

connects a ``NetClient`` to the parent's ``NetServer``, imports the
first-sync snapshot, then pushes ``rounds`` deterministic edit rounds;
after every PUSH_ACK it appends ``round epoch`` to
``<dir>/progress.log`` (fsynced — the parent's oracle for what was
ACKED) and atomically rewrites ``<dir>/frontier.bin`` (the encoded
resume frontier).  Then it writes ``<dir>/READY`` and sleeps — the
parent SIGKILLs it there.  This is a CPU-only client process (no
device work): a process that holds a chip is never signalled (docs/
RESILIENCE.md rule 2).

As a module (imported by the parent): ``apply_edit`` regenerates the
byte-identical edit stream and ``regen_replica`` rebuilds the child's
replica from the base doc + the acked round count.
"""
import os
import os.path as _p
import random
import sys
import time

sys.path.insert(0, _p.dirname(_p.dirname(_p.abspath(__file__))))  # repo root

CRASH_PEER = 7777


def apply_edit(d, rng):
    """One deterministic edit round (text + map + counter — enough to
    exercise multi-container payloads without state-order ambiguity)."""
    t = d.get_text("t")
    L = len(t)
    if L > 6 and rng.random() < 0.25:
        t.delete(rng.randrange(L - 2), 2)
    else:
        t.insert(rng.randint(0, L), rng.choice(["ab", "cd", "ef"]))
    d.get_map("m").set(rng.choice(["k", "j"]), rng.randrange(100))
    d.get_counter("c").increment(rng.randint(-3, 7))
    d.commit()


def regen_replica(base_doc, rounds, seed):
    """The parent-side oracle: the child's replica after ``rounds``
    acked rounds, rebuilt from the same base state + the same seeded
    edit stream."""
    from loro_tpu import LoroDoc

    d = LoroDoc(peer=CRASH_PEER)
    d.import_(base_doc.export_snapshot())
    rng = random.Random(seed)
    for _ in range(rounds):
        apply_edit(d, rng)
    return d


def main(argv):
    host, port, family, out_dir, rounds, seed = (
        argv[0], int(argv[1]), argv[2], argv[3], int(argv[4]), int(argv[5]))
    import jax

    jax.config.update("jax_platforms", "cpu")  # client-only: no devices
    from loro_tpu import LoroDoc
    from loro_tpu.net import NetClient

    d = LoroDoc(peer=CRASH_PEER)
    cli = NetClient(host, port, family, client_id="crash-child")
    cli.connect()
    d.import_(cli.pull(0))  # first-sync snapshot
    mark = d.oplog_vv()
    rng = random.Random(seed)
    progress = open(os.path.join(out_dir, "progress.log"), "a")
    for r in range(rounds):
        apply_edit(d, rng)
        payload = d.export_updates(mark)
        mark = d.oplog_vv()
        ack = cli.push(0, payload)
        cli.set_frontier(0, d.oplog_vv())
        # resume token FIRST, then the progress line: a crash between
        # the two leaves an acked round un-logged (safe: the parent
        # only asserts what the log claims), never a logged round
        # whose frontier was lost
        tmp = os.path.join(out_dir, "frontier.bin.tmp")
        with open(tmp, "wb") as f:
            f.write(cli.frontiers[0].encode())
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(out_dir, "frontier.bin"))
        progress.write(f"{r} {ack['epoch']}\n")
        progress.flush()
        os.fsync(progress.fileno())
    with open(os.path.join(out_dir, "READY"), "w") as f:
        f.write("ok")
    time.sleep(600)  # the parent SIGKILLs us here


if __name__ == "__main__":
    main(sys.argv[1:])
