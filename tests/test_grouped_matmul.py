"""ops/grouped_matmul.py — the dropless mixture's grouped matmul as a
kernel that visits (row tile, touched expert) pairs only (PR 38).

The kernel runs here under the Pallas interpreter, which the tests turn
on around themselves (``interpret=True``, or the module's switch for
the layer's own choice): interpret mode checks the arithmetic, the
metadata and the index maps, and cannot see VMEM limits or block-shape
rules — tests/test_chip_bringup.py compiles the three families' decode
programs and one prefill bucket each for a described v5e with the
kernel inside, in the one file that may describe a topology.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_held_init
from quintnet_tpu.ops import grouped_matmul as gm

# tiny lane-aligned cuts of the three families' expert shapes: (d, f)
# in the proportions of Ling-3.0-flash (2560, 768), openPangu-Ultra-MoE
# (7680, 2048) and Laguna-XS.2 (2048, 512)
FAMILIES = {"ling": (384, 128), "pangu": (512, 128), "laguna": (256, 128)}

# (name, sizes, rows, row tile): groups of 0, 1, 2, 3 and 70 rows with
# empty groups first, in the middle and last; no row held at all; a row
# count that is no multiple of the row tile; and the visits counted by
# hand — a group is visited once a row tile its rows reach into
CASES = [
    # rows 0 | 1-2 | 3-5 | 6-75: tile 0 four times, then tiles 1-4
    ("empty_first_middle_last", [0, 1, 2, 0, 3, 70, 0], 100, 16, 8),
    # one tile holds all four non-empty groups; 76 rows fill one of 128
    ("one_tall_tile", [0, 1, 2, 0, 3, 70, 0], 100, 128, 4),
    ("nobody_held", [0, 0, 0, 0], 40, 16, 0),
    # 50 rows are no multiple of 16: the last tile is partly out of range
    # (rows 0-2 | 3-47 | 48-49: tile 0 twice, tiles 1 and 2, tile 3)
    ("rows_off_the_tile", [3, 45, 2], 50, 16, 5),
    # every group ends on a tile's edge: one visit a group
    ("groups_on_the_edges", [16, 0, 32, 16], 64, 16, 4),
    ("all_rows_one_group", [0, 0, 64], 64, 32, 2),
]


def _by_loop(x, w, sizes):
    """The plain reference: one dense product a group, f32 sums."""
    out, start = [], 0
    for g, n in enumerate(sizes):
        out.append(jnp.dot(x[start:start + n], w[g],
                           preferred_element_type=jnp.float32))
        start += n
    return jnp.concatenate(out) if out else jnp.zeros((0, w.shape[-1]))


def _kernel_cases():
    """Every layout in both dtypes on one layer's weights, the family
    and the side (``[d, f]`` or ``[f, d]``) taking turns; then the
    first layout on a STACK at its first, middle and last layer, each
    family."""
    families = sorted(FAMILIES)
    for i, case in enumerate(CASES):
        for dtype in (jnp.bfloat16, jnp.float32):
            yield pytest.param(
                case, None, None, ("up", "down")[i % 2],
                families[i % 3], dtype,
                id=f"{case[0]}-{families[i % 3]}-{dtype.__name__}")
    for family in families:
        for layer, where in enumerate(("first", "middle", "last")):
            yield pytest.param(
                CASES[0], 3, layer, ("up", "down")[layer % 2], family,
                jnp.bfloat16, id=f"stack_{where}-{family}")


@pytest.mark.parametrize("case, layers, layer, side, family, dtype",
                         list(_kernel_cases()))
def test_kernel_is_the_grouped_matmul(case, layers, layer, side, family,
                                      dtype):
    """The kernel under the interpreter against ``lax.ragged_dot`` and
    against a per-group loop, over the groups' rows. The rows past
    every group go in as NaN and the OTHER layers of a stack hold NaN:
    a finite, equal result shows that neither was read into a group's
    row. The metadata's live visits equal the count made by hand."""
    _name, sizes, rows, tile, visits_by_hand = case
    d, f = FAMILIES[family]
    K, N = (d, f) if side == "up" else (f, d)
    rng = np.random.default_rng(len(sizes) * rows + tile)
    live = sum(sizes)
    x = rng.normal(size=(rows, K)).astype(np.float32)
    x[live:] = np.nan
    w = (rng.normal(size=(len(sizes), K, N)) / np.sqrt(K)).astype(np.float32)
    x, w = jnp.asarray(x, dtype), jnp.asarray(w, dtype)
    if layers is None:
        stack = w
    else:
        stack = jnp.full((layers, *w.shape), jnp.nan, dtype).at[layer].set(w)
    sizes_a = jnp.asarray(sizes, jnp.int32)

    visits = gm.group_visits(sizes_a, rows=rows, row_tile=tile)
    assert int(visits.count[0]) == visits_by_hand
    assert visits.group.shape == (-(-rows // tile) + len(sizes) - 1,)
    # a narrower column tile than the whole of N where N has two
    tn = 128 if N > 128 and side == "down" else None
    got = gm.grouped_matmul(
        x, stack, visits, layer=None if layers is None else jnp.int32(layer),
        row_tile=tile, column_tile=tn, interpret=True)
    assert got.shape == (rows, N) and got.dtype == jnp.float32

    want = lax.ragged_dot(x, w, sizes_a, preferred_element_type=jnp.float32)
    loop = _by_loop(x, w, sizes)
    assert bool(jnp.all(jnp.isfinite(got[:live])))
    # the same products and f32 sums in another order of summation
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(
        rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(got[:live], want[:live], **tol)
    np.testing.assert_allclose(got[:live], loop, **tol)


def test_visits_follow_the_rows_in_order():
    """``group_visits`` by hand on one layout: the (tile, group) pairs
    in row order, the last live pair repeated past the count (a dead
    grid step moves no block), offsets as a CSR's."""
    v = gm.group_visits(jnp.asarray([0, 1, 2, 0, 3, 70, 0]), rows=100,
                        row_tile=16)
    assert v.offsets.tolist() == [0, 0, 1, 3, 3, 6, 76, 76]
    assert int(v.count[0]) == 8
    assert v.group.tolist() == [1, 2, 4, 5, 5, 5, 5, 5] + [5] * 5
    assert v.tile.tolist() == [0, 0, 0, 0, 1, 2, 3, 4] + [4] * 5
    # nobody held: no live visit, every step names a block that exists
    v = gm.group_visits(jnp.zeros((4,), jnp.int32), rows=40, row_tile=16)
    assert int(v.count[0]) == 0
    assert set(v.group.tolist()) <= set(range(4))
    assert v.tile.tolist() == [0] * 6


def test_tiles_from_shapes():
    """The row tile from the static row count and the dtype's sublane
    packing, the column tile from the weight block's bytes, at the
    three cells' shapes; and the kernel's own VMEM sum inside what a
    call may ask for (ops/paged_attention.VMEM_CAP_BYTES)."""
    from quintnet_tpu.ops.paged_attention import VMEM_CAP_BYTES

    bf16 = jnp.bfloat16
    assert gm.row_tile_for(1536, bf16) == gm.ROW_TILE
    assert gm.row_tile_for(8192, bf16) == gm.ROW_TILE
    assert gm.row_tile_for(40, bf16) == 48 and gm.row_tile_for(40,
                                                             jnp.float32) == 40
    # Ling and Laguna whole, openPangu's in two column blocks
    assert gm.column_tile_for(2560, 768, bf16) == 768
    assert gm.column_tile_for(768, 2560, bf16) == 2560
    assert gm.column_tile_for(2048, 512, bf16) == 512
    assert gm.column_tile_for(7680, 2048, bf16) == 1024
    assert gm.column_tile_for(2048, 7680, bf16) == 3840
    assert gm.column_tile_for(2 ** 17, 128, bf16) is None
    for k, n in ((2560, 768), (768, 2560), (7680, 2048), (2048, 7680),
                 (2048, 512), (512, 2048)):
        held = gm.grouped_matmul_vmem_bytes(
            row_tile=gm.ROW_TILE, k=k, column_tile=gm.column_tile_for(
                k, n, bf16), x_dtype=bf16, w_dtype=bf16)
        assert held * 3 // 2 < VMEM_CAP_BYTES, (k, n, held)
    # which shapes take the kernel: whole lane tiles, a float the
    # kernel takes, rows in the weights' dtype, and a TPU (or the
    # interpreter a test turned on)
    ok = dict(k=256, n=128, x_dtype=bf16, w_dtype=bf16)
    assert gm.grouped_matmul_lowers_for("tpu", **ok)
    assert not gm.grouped_matmul_lowers_for("cpu", **ok)
    assert not gm.grouped_matmul_lowers_for("tpu", **{**ok, "k": 96})
    assert not gm.grouped_matmul_lowers_for("tpu", **{**ok, "n": 32})
    assert not gm.grouped_matmul_lowers_for(
        "tpu", **{**ok, "w_dtype": jnp.int8, "x_dtype": jnp.int8})
    assert not gm.grouped_matmul_lowers_for(
        "tpu", **{**ok, "x_dtype": jnp.float32})


def _layer(d, f, *, held=4, layers=None, dtype=jnp.bfloat16):
    args = MoEArgs(n_experts=8, top_k=2, dropless=True, scoring="sigmoid",
                   experts_held=(2, held))
    p = moe_held_init(jax.random.key(0), d, f, 8, held=held, dtype=dtype)
    if layers is not None:
        p["experts"] = jax.tree.map(
            lambda w: jnp.stack([w * (i + 1) for i in range(layers)]),
            p["experts"])
    return args, p


@pytest.mark.parametrize("d, f, kernel", [(256, 128, True), (96, 32, False),
                                          (256, 96, False)],
                         ids=["on_the_lane_grid", "tiny_preset",
                              "width_off_the_grid"])
def test_the_layer_chooses_from_shapes_where_it_lowers(d, f, kernel):
    """What ``nn/moe._expert_rows`` traces in a CPU process: on the
    128-lane grid BOTH forms under ``lax.platform_dependent`` — the
    kernel for a TPU, ``ragged_dot`` for everything else, so the CPU's
    own lowering holds no TPU custom call — and off the grid
    ``ragged_dot`` alone, with ``tile_visits`` 0."""
    args, p = _layer(d, f)
    x = jax.random.normal(jax.random.key(1), (2, 8, d), jnp.bfloat16)

    def run(p, x):
        return moe_apply(p, x, args, return_stats=True)

    jaxpr = str(jax.make_jaxpr(run)(p, x))
    assert "ragged_dot" in jaxpr
    assert ("grouped_matmul" in jaxpr) == kernel
    assert ("platform_index" in jaxpr) == kernel
    assert "tpu_custom_call" not in jax.jit(run).lower(p, x).as_text()
    _y, _aux, stats = jax.jit(run)(p, x)
    assert (float(stats["tile_visits"]) > 0) == kernel
    if kernel:
        # 16 routings in ONE row tile: a visit a touched expert
        assert float(stats["tile_visits"]) == float(stats["touched"])


@pytest.mark.parametrize("layers, layer", [(None, None), (3, 0), (3, 2)],
                         ids=["one_layer", "stack_first", "stack_last"])
def test_the_layer_through_the_kernel_is_the_layer_through_ragged_dot(
        monkeypatch, layers, layer):
    """The whole dropless layer with the kernel (interpreted: the
    module's switch on, so this process's backend and a TPU agree and
    no ``ragged_dot`` is traced) against the same layer as every CPU
    program runs it: same routings, same stats, outputs inside the
    rounding of bf16 operands summed in another order."""
    args, p = _layer(256, 128, layers=layers)
    x = jax.random.normal(jax.random.key(2), (3, 8, 256), jnp.bfloat16)
    mask = jnp.arange(24).reshape(3, 8) % 5 != 0

    def layer_fn():
        # (a function of its own a trace: JAX keeps a function's trace,
        # and the switch is read while tracing)
        return lambda p, x: moe_apply(
            p, x, args, return_stats=True, token_mask=mask,
            expert_layer=None if layers is None else jnp.int32(layer))

    y0, _, st0 = jax.jit(layer_fn())(p, x)
    monkeypatch.setattr(gm, "INTERPRET", True)
    jaxpr = str(jax.make_jaxpr(layer_fn())(p, x))
    assert "grouped_matmul" in jaxpr and "ragged_dot" not in jaxpr
    y1, _, st1 = jax.jit(layer_fn())(p, x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32),
                               rtol=2e-2, atol=2e-2)
    for k in st0:
        np.testing.assert_allclose(st1[k], st0[k], err_msg=k)
    assert float(st1["tile_visits"]) == float(st1["touched"]) > 0
