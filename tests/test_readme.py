"""The README quick-start must actually run (reference: crates/loro/
tests/readme.rs keeps doc examples honest)."""
import re
from pathlib import Path


def test_readme_quickstart_executes():
    readme = Path(__file__).parent.parent / "README.md"
    blocks = re.findall(r"```python\n(.*?)```", readme.read_text(), re.S)
    assert blocks, "README lost its python examples"
    ns: dict = {}
    # quick-start block is self-contained; the fleet block needs doc
    # fixtures, so provide them
    exec(blocks[0], ns)  # noqa: S102 - executing our own README
    assert ns["a"].get_deep_value() == ns["b"].get_deep_value()

    import loro_tpu as lt

    docs = []
    for i in range(3):
        d = lt.LoroDoc(peer=50 + i)
        d.get_text("t").insert(0, f"readme {i}")
        d.commit()
        docs.append(d)
    from loro_tpu.ops.columnar import extract_map_ops

    for d in docs:
        d.get_map("m").set("k", int(d.peer))
        d.commit()
    ns2 = {
        "payloads": [d.export_updates()[10:] for d in docs],
        "sync_rounds": [],  # illustrative in the README; empty here
        "container_id": docs[0].get_text("t").id,
        "changes_per_doc": [d.oplog.changes_in_causal_order() for d in docs],
        "cid": docs[0].get_text("t").id,
        "new_changes_per_doc": [d.oplog.changes_in_causal_order() for d in docs],
        "extracts": [extract_map_ops(d.oplog.changes_in_causal_order()) for d in docs],
    }
    fleet_block = blocks[1]
    # shrink the illustrative capacities so the smoke run is fast
    fleet_block = fleet_block.replace("n_docs=4096", "n_docs=3").replace(
        "capacity=1 << 14", "capacity=1024"
    )
    exec(fleet_block, ns2)  # noqa: S102
    assert ns2["texts"] == [d.get_text("t").to_string() for d in docs]
