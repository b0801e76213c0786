"""Compile the main path's kernels and steps for a *described* TPU v5e.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): it raises
what the chip's compiler would raise — a gather Mosaic cannot lower, a
program that does not fit 16 GB, a kernel that cannot be partitioned —
and costs no chip time.  Nothing runs, so nothing here says a result is
right or fast.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never while a
module is imported), every compile happens in this process, and all of
these tests live in this one file, because one process at a time may load
the TPU library.  xdist keeps a file on one worker only under ``--dist
loadfile`` (the driver's command) or ``loadgroup`` (the ``xdist_group``
mark below); under a plain ``-n N`` several workers load the library and
all but one fail at its lockfile.  That is a failure here, not a skip:
the fixture skips only where the TPU library is not installed, so the
Mosaic gate cannot vanish silently under another runner.

Code that asks ``jax.default_backend()`` sees the CPU here and would take
its CPU branch (XLA rank, interpret-mode kernel), which proves nothing
about Mosaic.  The ``tpu_branches`` fixture steers it in the test, not
through a program option; kernels are also called with
``interpret=False`` directly.

Fast cases (kernels, the rank half of the flagship step, the kernel under
``shard_map`` on four devices) run in tier-1.  Whole steps at real width
take 20-70 s each to compile (the TPU's sort lowering, whatever the doc
axis), so they are marked ``slow``; run them with

    python -m pytest tests/test_chip_compile.py -m slow -s

and paste what they print into CHANGES.md when the shapes change.
"""
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from loro_tpu.ops import fugue_batch as fb
from loro_tpu.ops.pallas_rank import wyllie_rank
from loro_tpu.parallel.mesh import DOC_AXIS, OP_AXIS

pytestmark = pytest.mark.xdist_group("chip_compile")

HBM_BYTES = 16e9  # one v5e chip

# the rings of the flagship step: the real automerge trace contracted to
# pad_c 18,432 (m = 36,866, the packed kernel); the seeded trace that
# chip_smoke.py generates contracts to pad_c 51,200
# (m = 102,402, past the 16-bit domain: the wide kernel)
M_REAL, M_SEEDED = 36_866, 102_402
PAD_C, PAD_N = 51_200, 237_568  # the seeded trace's flagship shapes


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU library (libtpu) is not installed here")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # whatever this raises (the library's lockfile under several
    # workers, ABORTED, ...) fails the tests: it is not a reason to skip
    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), (DOC_AXIS, OP_AXIS))


@pytest.fixture
def tpu_branches(monkeypatch):
    """Make ``jax.default_backend()`` answer "tpu": the auto rank spec
    then resolves to Pallas and the kernel leaves interpret mode, as on
    the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def compile_checked(name, lowered, expect_kernel):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    print(json.dumps({
        "compiled": name, "compile_s": round(seconds, 1),
        "tpu_custom_call": "tpu_custom_call" in text,
        "all_gather": "all-gather" in text,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "GiB": round(total / 2**30, 3),
    }))
    assert ("tpu_custom_call" in text) == expect_kernel, name
    assert total < HBM_BYTES, f"{name}: {total} bytes do not fit one chip"
    return text


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def seq_sds(d, n, sh):
    dts = (jnp.int32, jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.bool_)
    return fb.SeqColumns(*[sds((d, n), dt, sh) for dt in dts])


def sequ_sds(d, n, sh):
    dts = (jnp.int32, jnp.int32, jnp.uint32, jnp.uint32, jnp.int32, jnp.bool_,
           jnp.int32, jnp.bool_)
    return fb.SeqColumnsU(*[sds((d, n), dt, sh) for dt in dts])


def chain_sds(d, c, n, sh):
    return fb.ChainColumns(
        c_parent=sds((d, c), jnp.int32, sh), c_side=sds((d, c), jnp.int32, sh),
        c_valid=sds((d, c), jnp.bool_, sh), head_row=sds((d, c), jnp.int32, sh),
        chain_id=sds((d, n), jnp.int32, sh), deleted=sds((d, n), jnp.bool_, sh),
        content=sds((d, n), jnp.int32, sh), valid=sds((d, n), jnp.bool_, sh))


# ---------------------------------------------------------------------------
# the kernels (a second or two each)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [
    M_REAL,    # the packed ruling-set kernel: what the cells launch
    M_SEEDED,  # 65,536 < m <= 131,072: the dual-table wide kernel
    # a small ring (DeviceDocBatch's solver starts at a 256-chain budget,
    # m = 514): padded to two rows per table, which Mosaic needs
    514,
])
def test_rank_kernel_compiles(one_chip, m):
    fn = jax.jit(jax.vmap(lambda s: wyllie_rank(s, interpret=False)))
    compile_checked(f"wyllie_rank:vmap8:m{m}",
                    fn.lower(sds((8, m), jnp.int32, one_chip)), True)


def test_flagship_rank_half_compiles_with_the_kernel(one_chip, tpu_branches):
    """The rank half of the step ``import`` (b) launches, at the seeded
    trace's chain shapes, through the auto spec: ring build (the sibling
    sort over 51,200 chains) + the kernel.  The placement half is a
    237,568-row sort whose compile alone takes ~25 s: see the slow case."""
    assert fb._resolve_rank_spec(None, 2 * (PAD_C + 1)) == ("pallas", "ruling")

    def rank_half(c: fb.ChainColumns):
        crank = fb._order_core(c.c_parent, c.c_side, c.c_valid)
        return crank.astype(jnp.uint32).sum(dtype=jnp.uint32)

    lowered = jax.jit(jax.vmap(rank_half)).lower(chain_sds(8, PAD_C, 8, one_chip))
    compile_checked(f"chain_rank_checksum:[8,c{PAD_C}]", lowered, True)


# ---------------------------------------------------------------------------
# four devices: the kernel under shard_map, and why it must be there
# ---------------------------------------------------------------------------


def test_fleet_step_on_four_devices_keeps_the_kernel(mesh4, tpu_branches):
    """The step ``Fleet.merge_text_docs`` launches on a doc-axis mesh of
    four chips (``chain_merge_docs_packed`` on rows put with
    ``doc_sharding``): the vmapped batch runs under shard_map, so every
    device ranks its own documents with the kernel and nothing is
    gathered across chips."""
    from loro_tpu.parallel.fleet import text_pads, text_transport

    pad_c, pad_n = text_pads(900, 2048)
    assert text_transport(pad_c, pad_n) == "packed"
    assert fb._resolve_rank_spec(None, fb.rank_bound(pad_c))[0] == "pallas"
    rows = sds((8, fb.packed_row_bytes(pad_c, pad_n)), jnp.uint8,
               NamedSharding(mesh4, P(DOC_AXIS)))
    text = compile_checked(
        f"Fleet.text_step:mesh4:[8,{pad_n}]:c{pad_c}",
        fb.chain_merge_docs_packed.lower(rows, pad_c, pad_n), True)
    assert "all-gather" not in text and "all-reduce" not in text


def test_a_plain_jit_on_four_devices_is_refused(mesh4, tpu_branches):
    """What ``shard_docs`` is for: the same batch function in a plain jit
    with doc-sharded inputs is refused at lowering — loudly; it never
    falls back to the XLA rank."""
    sh = NamedSharding(mesh4, P(DOC_AXIS))
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(fb.materialize_content_batch).lower(seq_sds(8, 8192, sh))


@pytest.mark.parametrize("where,want_eff", [("one_chip", False), ("mesh4", True)])
def test_tree_import_launch_compiles_with_the_fused_replay(
        request, tpu_branches, where, want_eff):
    """The one launch of ``Fleet``'s tree entry at upstream's bench shape
    (256 documents, 1,000 nodes, 98,304 padded moves; benchmark cell
    ``tree_import.fleet256``): on one chip as the payload entry launches
    it, and under shard_map on four with the moves effected returned
    (``merge_tree_children``).  The replay is the Pallas kernel, and no
    device talks to another."""
    from loro_tpu.ops import tree_batch as tb

    assert tb.replay_algo(1000) == "pallas:lockstep"
    m = tb.tree_pads(97_700)
    place = request.getfixturevalue(where)
    sh = place if where == "one_chip" else NamedSharding(place, P(DOC_AXIS))
    text = compile_checked(
        f"tree_import_batch:{where}:[256,1+{m}]:n1000:eff={want_eff}",
        tb.tree_import_batch.lower(sds((256, 1 + m), jnp.uint32, sh), 1000, want_eff),
        True)
    assert "all-gather" not in text and "all-reduce" not in text


# ---------------------------------------------------------------------------
# whole steps at real width (slow: 20-70 s of compile each)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_flagship_step_compiles_at_real_width(one_chip, tpu_branches):
    """``import`` (b): chain_merge_docs_packed_checksum at [8, 237568],
    pad_c 51,200 — unpack, ring, kernel, placement sort."""
    lowered = fb.chain_merge_docs_packed_checksum.lower(
        sds((8, fb.packed_row_bytes(PAD_C, PAD_N)), jnp.uint8, one_chip),
        PAD_C, PAD_N)
    compile_checked(f"chain_merge_docs_packed_checksum:[8,{PAD_N}]:c{PAD_C}",
                    lowered, True)


@pytest.mark.slow
@pytest.mark.parametrize("chains,elements,pads,transport", [
    # 16 B4-sized documents (the benchmark's): ring 65,536, packed rows
    (17_500, 182_315, (32_767, 262_144), "packed"),
    # the seeded trace of chip_smoke.py: ~51,000 chains, a
    # chain bucket past 16-bit ids, ring 131,072 = PALLAS_RANK_MAX_M
    (51_000, 233_894, (65_535, 262_144), "chains"),
])
def test_public_step_compiles_at_real_width(topo, tpu_branches, chains, elements,
                                            pads, transport):
    """``import`` (a): the step ``Fleet.merge_text_payloads`` launches
    for 16 documents, on arrays put with the mesh's doc sharding —
    ``chain_merge_docs_packed`` on u8 rows, or the ``ChainColumns`` step
    where the chain bucket outgrows them.  The Pallas rank either way:
    the kernel IS in the program."""
    from loro_tpu.parallel.fleet import text_pads, text_transport
    from loro_tpu.parallel.mesh import doc_sharding, make_mesh

    sh = doc_sharding(make_mesh([topo.devices[0]]))
    pad_c, pad_n = text_pads(chains, elements)
    assert (pad_c, pad_n) == pads and text_transport(pad_c, pad_n) == transport
    assert fb._resolve_rank_spec(None, fb.rank_bound(pad_c)) == ("pallas", "ruling")
    if transport == "packed":
        lowered = fb.chain_merge_docs_packed.lower(
            sds((16, fb.packed_row_bytes(pad_c, pad_n)), jnp.uint8, sh), pad_c, pad_n)
    else:
        lowered = fb._chain_merge_docs_jit.lower(chain_sds(16, pad_c, pad_n, sh))
    compile_checked(f"Fleet.text_step:{transport}:[16,{pad_n}]:c{pad_c}", lowered, True)


@pytest.mark.parametrize("devices,d,cap,k_pad,width", [
    (1, 144, 262_144, 16, 262_144),   # b4_resident.coldstart16's round
    (1, 4096, 16_384, 1024, 16),      # keystroke-sized rounds, a served table
    (4, 144, 262_144, 16, 262_144),   # the table doc-sharded, the block replicated
], ids=["coldstart16", "keystrokes", "mesh4"])
def test_resident_scatter_moves_the_named_block_and_no_table(
        topo, devices, d, cap, k_pad, width):
    """``_scatter_rows`` (ISSUE 36): one loop over the block's rows, every
    table buffer updated in place — the program's temporaries are smaller
    than ONE table column, so there is no ``[d, capacity]`` copy and no
    ``[k_pad, capacity]`` gather — and on four devices no all-gather."""
    from loro_tpu.parallel.fleet import _scatter_rows

    mesh = Mesh(np.array(topo.devices[:devices]).reshape(devices, 1),
                (DOC_AXIS, OP_AXIS))
    sh, rep = NamedSharding(mesh, P(DOC_AXIS)), NamedSharding(mesh, P())
    state = (sequ_sds(d, cap, sh), sds((d, cap), jnp.uint32, sh),
             sds((d, cap), jnp.uint32, sh))
    blk = dict(zip(fb.SeqColumnsU._fields, sequ_sds(k_pad, width, rep)))
    blk["key_hi"] = blk["key_lo"] = sds((k_pad, width), jnp.uint32, rep)
    idx = sds((k_pad,), jnp.int32, rep)
    compiled = _scatter_rows.lower(state, blk, idx, idx, mesh).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    table = 34 * (d // devices) * cap  # a device's share of the table
    print(json.dumps({"compiled": f"_scatter_rows:{devices}:[{d},{cap}]:[{k_pad},{width}]",
                      "temp_bytes": mem.temp_size_in_bytes,
                      "alias_bytes": mem.alias_size_in_bytes, "table_bytes": table}))
    assert mem.alias_size_in_bytes >= table  # donated: written in place
    assert mem.temp_size_in_bytes < (d // devices) * cap  # under one bool column
    assert "all-gather" not in text and text.count(" while(") == 1


@pytest.mark.slow
def test_resident_materialise_compiles_at_real_size(one_chip):
    """``serve``: materialize_by_key over the 4096 x 16,384 resident
    ``SeqColumnsU`` — watch the sort temporaries."""
    d, n = 4096, 16_384
    lowered = fb.materialize_by_key.lower(
        sequ_sds(d, n, one_chip), sds((d, n), jnp.uint32, one_chip),
        sds((d, n), jnp.uint32, one_chip))
    compile_checked(f"materialize_by_key:[{d},{n}]", lowered, False)


@pytest.mark.slow
def test_export_select_compiles_at_the_top_of_its_warm_ladder(one_chip):
    """``serve``: the read plane's selection at 256 requests over the
    4096-document index (capacity 256, frontier width 4)."""
    from loro_tpu.ops.export_batch import _select_fn

    r, f, d, cap = 256, 4, 4096, 256
    args = [sds((r,), jnp.int32, one_chip), sds((r, f), jnp.uint32, one_chip),
            sds((r, f), jnp.uint32, one_chip), sds((r, f), jnp.int32, one_chip),
            sds((r,), jnp.int32, one_chip)]
    args += [sds((d, cap), dt, one_chip) for dt in
             (jnp.uint32, jnp.uint32, jnp.int32, jnp.int32, jnp.int32)]
    args.append(sds((d,), jnp.int32, one_chip))
    compile_checked(f"export_select:r{r}:[{d},{cap}]", _select_fn().lower(*args), False)


@pytest.mark.slow
def test_resident_solver_on_four_devices_keeps_the_kernel(mesh4, tpu_branches):
    """``DeviceDocBatch._materialize(use_solver=True)`` on the mesh:
    chain_merge_docs_u at [8, 32768], chain budget 4096."""
    sh = NamedSharding(mesh4, P(DOC_AXIS))
    text = compile_checked(
        "chain_merge_docs_u:mesh4:[8,32768]:c4096",
        fb._chain_merge_docs_u_jit.lower(sequ_sds(8, 32_768, sh), 4096), True)
    assert "all-gather" not in text
