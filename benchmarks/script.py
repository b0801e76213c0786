"""The seeded edit script of one import document: B4-shaped patches,
routed over concurrent peers.  Plain Python over the standard library:
both the generator of the fed data (``gen.make_payload``, which types the
script into the program's replicas) and the plain reference
(``fugue_reference.replay``) read the script from here and share nothing
else.

Shapes come from the configuration file (``configs/b4_import.json``):
``insert_patches`` / ``delete_patches`` are met exactly, run lengths are
drawn from ``insert_run`` / ``delete_run``, and nothing is deleted before
the first exchange (a replica that has typed nothing has nothing to
delete).
"""
from __future__ import annotations

import random

_LETTERS = "etaoin shrdlu"


def b4_shaped_patches(seed: int, c: dict) -> list:
    """``(pos, char)`` single-character patches, ``char == ""`` for a
    delete: bursts at fresh positions, either a typing run (consecutive
    positions) or a backspace run (descending positions).  Exactly
    ``insert_patches`` inserts and ``delete_patches`` deletes; the kind of
    each burst is drawn so that both run out together."""
    rng = random.Random(seed)
    ins_left, del_left = c["insert_patches"], c["delete_patches"]
    (ilo, ihi), (dlo, dhi) = c["insert_run"], c["delete_run"]
    ins_mean, del_mean = (ilo + ihi) / 2, (dlo + dhi) / 2
    quiet = c["sync_every_patches"]  # inserts only, up to the first exchange
    out, length = [], 0
    while ins_left or del_left:
        want_del = del_left / del_mean
        delete = (len(out) >= quiet and length > 2 * dhi and del_left > 0 and (
            ins_left == 0
            or rng.random() < want_del / (want_del + ins_left / ins_mean)))
        if delete:
            pos = rng.randrange(dhi, length + 1)  # the cursor: deletes pos-1, pos-2, ...
            run = min(rng.randint(dlo, dhi), del_left)
            out.extend((pos - 1 - j, "") for j in range(run))
            length -= run
            del_left -= run
        else:
            if ins_left == 0:  # only deletes are left and the text is short
                raise ValueError("the patch counts leave deletes with nothing to delete")
            pos = rng.randrange(length + 1)
            run = min(rng.randint(ilo, ihi), ins_left)
            out.extend((pos + j, _LETTERS[rng.randrange(13)]) for j in range(run))
            length += run
            ins_left -= run
    return out


def routed_patches(seed: int, c: dict, v: int) -> list:
    """Variant ``v`` of the script: ``(peer, pos, char)``.  The stream is
    routed across ``peers_per_document`` replicas in windows of
    ``peer_window`` patches; all replicas exchange everything after every
    ``sync_every_patches`` patches and at the end.  A replica applies a
    patch at ``min(pos, its own length)`` (``its own length - 1`` for a
    delete): between exchanges the replicas' texts differ."""
    rng = random.Random(seed * 1_000_003 + 0xBE5C + v)
    lo, hi = c["peer_window"]
    out, cur, left = [], 0, 0
    for pos, ch in b4_shaped_patches(seed, c):
        if left == 0:
            cur = rng.randrange(c["peers_per_document"])
            left = rng.randint(lo, hi)
        left -= 1
        out.append((cur, pos, ch))
    return out
