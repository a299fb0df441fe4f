"""The dropless path of a rank that is told which experts it holds
(``parallel/moe.py`` ``held_experts_layer``) and the shares of a layer
``models/solar.py`` computes, on the CPU: against a dense loop over the
experts, under a skewed router, and share by share against the whole
layer of the plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.catalog import Catalog
from horovod_tpu.common import faults as faults_lib
from horovod_tpu.models import solar
from horovod_tpu.parallel import moe

T, D, F, E, K = 64, 16, 24, 16, 4
REFERENCE = Catalog().module("reference", "solar_open2")


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return {"x": jax.random.normal(ks[0], (T, D)),
            "router": jax.random.normal(ks[1], (D, E)),
            "gate": jax.random.normal(ks[2], (E, D, F)) * 0.2,
            "up": jax.random.normal(ks[3], (E, D, F)) * 0.2,
            "down": jax.random.normal(ks[4], (E, F, D)) * 0.2}


def _held(p, held, block_rows=None, router=None):
    a, b = held[0], held[0] + held[1]
    return moe.held_experts_layer(
        p["x"], p["router"] if router is None else router, p["gate"][a:b],
        p["up"][a:b], p["down"][a:b], E, held, K, block_rows=block_rows)


def _dense(x, router, gate, up, down, held):
    """Every held expert on every token, weighted by the token's
    normalised top-k score for it (0 where it was not chosen)."""
    scores, experts = jax.lax.top_k(jax.nn.softmax(x @ router, -1), K)
    weights = scores / scores.sum(-1, keepdims=True)
    y = 0.0
    for e in range(held[0], held[0] + held[1]):
        w = (weights * (experts == e)).sum(-1)
        y = y + w[:, None] * (
            (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return y


# held experts, rows a block: every expert in one block; a share in blocks
# so small that the loop runs eight times; the default block; a share at
# the end of the router's columns.
@pytest.mark.parametrize("held, block_rows", [
    ((0, 16), None), ((4, 4), 8), ((4, 4), None), ((12, 4), 16)])
def test_the_held_experts_part_and_its_gradients(layer, held, block_rows):
    keys = ("x", "router", "gate", "up", "down")

    def ours(*args):
        return _held(dict(zip(keys, args)), held, block_rows)[0]

    def dense(*args):
        return _dense(*args, held)

    args = [layer[k] for k in keys]
    with jax.default_matmul_precision("highest"):
        y, stats = _held(layer, held, block_rows)
        want = dense(*args)
        got_grads = jax.grad(lambda *a: (ours(*a) ** 2).sum(),
                             argnums=range(5))(*args)
        want_grads = jax.grad(lambda *a: (dense(*a) ** 2).sum(),
                              argnums=range(5))(*args)
    np.testing.assert_allclose(y, want, atol=2e-6)
    for got, wanted in zip(got_grads, want_grads):
        np.testing.assert_allclose(got, wanted, atol=2e-5)
    # the counts are the router's own
    experts = moe.route_top_k(layer["x"], layer["router"], K)[0]
    demanded = [(experts == e).sum() for e in range(held[0], sum(held))]
    assert stats["expert_load"].tolist() == demanded
    assert float(stats["local_routes"]) == sum(demanded)
    assert float(stats["dropped_tokens"]) == 0.0


# held experts, rows a block: the default block with room to spare; blocks
# so small that the routes spill past the first, into one further block
# and into several; a share at the end of the router's columns.
@pytest.mark.parametrize("held, block_rows", [
    ((0, 16), None), ((4, 4), 40), ((4, 4), 8), ((4, 4), None),
    ((12, 4), 16)])
def test_whole_blocks_give_what_the_ladder_gives(layer, held, block_rows):
    """``whole_blocks``: the first ``block_rows`` routes are one block
    whose groups fill it (the rows no route fills are the last held
    expert's, at weight 0), the routes past it go through the loop in
    blocks a fifth that size, filled the same way. The layer's result, every gradient
    and the routes' stats are those of the ladder."""
    a, b = held[0], sum(held)
    keys = ("x", "router", "gate", "up", "down")

    def run(whole, *args):
        p = dict(zip(keys, args))
        return moe.held_experts_layer(
            p["x"], p["router"], p["gate"][a:b], p["up"][a:b],
            p["down"][a:b], E, held, K, block_rows=block_rows,
            whole_blocks=whole)

    args = [layer[k] for k in keys]
    seen = []
    grouped = moe._grouped_experts

    def spy(rungs, *rest):
        seen.append((rungs, int(rest[-2].sum()), rest[-1]))
        return grouped(rungs, *rest)

    with jax.default_matmul_precision("highest"):
        want, want_stats = run(False, *args)
        moe._grouped_experts = spy
        try:
            got, got_stats = run(True, *args)
        finally:
            moe._grouped_experts = grouped
        grads = [jax.grad(lambda *a: (run(whole, *a)[0] ** 2).sum(),
                          argnums=range(5))(*args) for whole in (True, False)]
    np.testing.assert_allclose(got, want, atol=1e-6)
    for ours, theirs in zip(*grads):
        np.testing.assert_allclose(ours, theirs, atol=1e-5)
    for k in ("expert_load", "local_routes", "dropped_tokens"):
        assert got_stats[k].tolist() == want_stats[k].tolist()
    block = block_rows or moe.default_block_rows(T, K, held[1], E)
    small = moe._spill_rows(block)
    routes = int(want_stats["local_routes"])
    spilled = -(-max(routes - block, 0) // small) * small
    # one rung: no ladder; the groups fill every block that runs, the
    # first of ``block`` rows and the loop's of ``small``
    assert seen == [((block,), block + spilled, small)]
    assert float(got_stats["worked_rows"]) == block + spilled


def test_a_whole_block_works_on_the_same_rows_whatever_the_routes(layer):
    """What ``whole_blocks`` is for. A router that sends the held experts
    next to nothing and a balanced one hand the grouped matmuls the same
    40,960 rows (the ladder takes its lower rung, 19,456, for both); a
    router that sends them nearly every token's four routes (59,648, past
    the block) pays three further blocks of 8,192 rows, where the
    ladder's loop pays a second block of 40,960."""
    x = jnp.abs(jnp.tile(layer["x"], (256, 1)))       # 16,384 rows
    shifted = {name: layer["router"].at[:, 4:8].add(by)
               for name, by in (("none", -1.0), ("fair", 0.0), ("all", 1.0))}
    stats = {(whole, name): moe.held_experts_layer(
        x, router, layer["gate"][4:8], layer["up"][4:8], layer["down"][4:8],
        E, (4, 4), K, whole_blocks=whole)[1]
        for whole in (False, True) for name, router in shifted.items()}
    routes = {k: float(v["local_routes"]) for k, v in stats.items()}
    worked = {k: float(v["worked_rows"]) for k, v in stats.items()}
    block = moe.default_block_rows(256 * T, K, 4, E)
    assert block == 40960 and moe._spill_rows(block) == 8192
    assert routes[True, "none"] < 1000 < routes[True, "fair"] < block
    assert block + 2 * 8192 < routes[True, "all"] <= block + 3 * 8192
    assert routes == {(w, n): routes[True, n] for w, n in routes}
    assert worked[True, "none"] == worked[True, "fair"] == block
    assert worked[False, "none"] == worked[False, "fair"] == 19456
    assert worked[True, "all"] == block + 3 * 8192
    assert worked[False, "all"] == 2 * block
    assert all(float(v["dropped_tokens"]) == 0 for v in stats.values())


def test_the_shares_add_up_to_the_whole_routed_layer(layer):
    """16 experts in 4 shares of 4: the parts the four ranks compute add
    up to the part of a rank that holds every expert."""
    with jax.default_matmul_precision("highest"):
        parts = [_held(layer, (first, 4)) for first in (0, 4, 8, 12)]
        whole, stats = _held(layer, (0, 16))
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, atol=2e-6)
    assert sum(float(s["local_routes"]) for _, s in parts) == T * K \
        == float(stats["local_routes"])


def test_no_route_is_dropped_under_a_skewed_router(layer):
    """``chaos_skew_gate`` drives every token's first choice to expert 5:
    the share that holds it computes all T routes to it, block after
    block of 16 rows; the capacity path's arithmetic
    (1.25 T k / E = 20 rows an expert) would have dropped two thirds of
    them."""
    faults_lib.install(faults_lib.FaultPlan.from_json(
        '{"seed": 1, "faults": [{"site": "moe_skew", "step": 1, '
        '"scale": 30.0, "target": "5"}]}'))
    try:
        router = moe.chaos_skew_gate(layer["router"])
    finally:
        faults_lib.uninstall()
    assert float(router[:, 5].min()) > float(layer["router"].max())
    # 2.5 x the 64 routes a balanced router sends to 4 of 16 experts
    assert moe.default_block_rows(T, K, 4, E) == 160
    # the gate skews a router's WEIGHTS: tokens with positive features
    # all score the hot column highest
    layer = {**layer, "x": jnp.abs(layer["x"])}
    with jax.default_matmul_precision("highest"):
        y, stats = _held(layer, (4, 4), block_rows=16, router=router)
        want = _dense(layer["x"], router, layer["gate"], layer["up"],
                      layer["down"], (4, 4))
    assert stats["expert_load"][1] == T     # over the capacity path's 20
    assert float(stats["local_routes"]) >= 4 * 16   # four blocks ran
    assert float(stats["dropped_tokens"]) == 0.0
    np.testing.assert_allclose(y, want, atol=2e-6)


def test_block_rows_follow_the_expected_routes():
    # the cell: 8192 tokens, top-8, 8 of 320 held: 1,638 routes expected
    assert moe.default_block_rows(8192, 8, 8, 320) == 4096
    # never more rows than there can be routes
    assert moe.default_block_rows(16, 4, 16, 16) == 64
    # the gated-convolution cell: 16,384 tokens, top-4, 8 of 32 held:
    # 16,384 routes expected, and two and a half times that a block
    assert moe.default_block_rows(16384, 4, 8, 32) == 40960


# -- the first block's rungs -------------------------------------------------

# tokens, top_k, held, experts -> the rungs: the gated-convolution cell
# (16,384 expected routes: 1.1875 / 2.5 times them); Solar's cell (1,638
# expected: 2,458 rows of headroom pay for no second copy); this file's
# layer and a block capped at the routes there can be (headroom under
# 4,096 rows: one rung); ten times the cell's tokens (a third rung fits
# 4,096 rows under the second)
@pytest.mark.parametrize("shape, rungs", [
    ((16384, 4, 8, 32), (19456, 40960)),
    ((8192, 8, 8, 320), (4096,)),
    ((T, K, 4, E), (160,)),
    ((16, 4, 16, 16), (64,)),
    ((163840, 4, 8, 32), (167936, 194560, 409600))])
def test_the_first_blocks_rungs_follow_the_expected_routes(shape, rungs):
    tokens, top_k, held, experts = shape
    block = moe.default_block_rows(*shape)
    got = moe.first_block_rungs(tokens * top_k * held / experts, block)
    assert got == rungs and got[-1] == block
    assert all(r % 512 == 0 for r in got[:-1])
    assert all(b - a >= 4096 for a, b in zip(got, got[1:]))
    assert all(r >= tokens * top_k * held / experts for r in got)


def _routes(n, seed=3):
    """``n`` routes of the layer's T tokens to 4 experts, sorted by
    expert and padded as ``held_experts_layer`` hands them on."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    experts = jnp.sort(jax.random.randint(ks[0], (n,), 0, 4))
    tokens = jax.random.randint(ks[1], (n,), 0, T).astype(jnp.int32)
    weights = jax.random.uniform(ks[2], (n,), minval=0.1)
    return experts, tokens, weights


LADDER = (16, 32, 48)


# routes inside each rung, at a rung's edge and one past it, none at all,
# and past the last rung: the loop still runs (three blocks at 100)
@pytest.mark.parametrize("n", [0, 5, 16, 17, 30, 32, 33, 48, 49, 100])
def test_every_rung_gives_the_dense_result_and_its_gradients(layer, n):
    experts, tokens, weights = _routes(n)
    sizes = jnp.bincount(experts, length=4).astype(jnp.int32)
    pad = -n % LADDER[-1] if n else LADDER[-1]
    banks = [layer[k][4:8] for k in ("gate", "up", "down")]

    def ours(x, gate, up, down, w):
        return moe._grouped_experts(
            LADDER, x, gate, up, down, jnp.pad(w, (0, pad)),
            jnp.pad(tokens, (0, pad)), sizes)

    def dense(x, gate, up, down, w):
        xg = x[tokens]
        h = jax.nn.silu(jnp.einsum("nd,ndf->nf", xg, gate[experts])) \
            * jnp.einsum("nd,ndf->nf", xg, up[experts])
        y = jnp.einsum("nf,nfd->nd", h, down[experts]) * w[:, None]
        return jnp.zeros_like(x).at[tokens].add(y)

    args = (layer["x"], *banks, weights)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(*args), dense(*args), atol=2e-6)
        got = jax.grad(lambda *a: (ours(*a) ** 2).sum(), range(5))(*args)
        want = jax.grad(lambda *a: (dense(*a) ** 2).sum(), range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


# held experts under a ladder of (8, 24, block): a share whose routes sit
# in the lowest rung, in the middle one, in the block itself; and a hot
# expert whose routes overflow the block into the loop
@pytest.mark.parametrize("held, hot", [
    ((5, 1), False), ((4, 1), False), ((0, 16), False), ((5, 1), True)])
def test_worked_rows_are_the_rung_taken_and_the_loops_blocks(
        layer, monkeypatch, held, hot):
    monkeypatch.setattr(moe, "first_block_rungs",
                        lambda expected, block: (8, 24, block))
    router = layer["router"]
    if hot:
        layer = {**layer, "x": jnp.abs(layer["x"])}
        router = router.at[:, 5].add(30.0)
    block = moe.default_block_rows(T, K, held[1], E)
    with jax.default_matmul_precision("highest"):
        y, stats = _held(layer, held, router=router)
        want = _dense(layer["x"], router, layer["gate"], layer["up"],
                      layer["down"], held)
    np.testing.assert_allclose(y, want, atol=2e-6)
    routes = int(stats["local_routes"])
    rung = next((r for r in (8, 24) if routes <= r), block)
    more = max(-(-routes // block), 1) - 1
    assert (more > 0) == hot
    assert float(stats["worked_rows"]) == rung + more * block
    assert float(stats["dropped_tokens"]) == 0.0
    # an explicit block is one rung, whatever the rule would give
    assert float(_held(layer, held, block, router)[1]["worked_rows"]) \
        == (more + 1) * block


def _lowered_for_the_tpu(tokens, hidden, width, experts, top_k, **kw):
    """The layer and its gradients at a cell's shapes (8 held experts,
    bf16 rows, fp32 banks), lowered for the TPU and not run: its text."""
    def loss(x, router, gate, up, down):
        y = moe.held_experts_layer(x, router, gate, up, down, experts,
                                   (0, 8), top_k, **kw)[0]
        return (y.astype(jnp.float32) ** 2).sum()

    shape = jax.ShapeDtypeStruct
    args = (shape((tokens, hidden), jnp.bfloat16),
            shape((hidden, experts), jnp.float32),
            shape((8, hidden, width), jnp.float32),
            shape((8, hidden, width), jnp.float32),
            shape((8, width, hidden), jnp.float32))
    return jax.jit(jax.grad(loss, range(5))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _count(text):
    return (text.count('"stablehlo.case"'), text.count('"chlo.ragged_dot"'))


def test_solars_cell_holds_no_conditional_and_the_other_cell_two(
        monkeypatch):
    solar_cell = (8192, 4096, 1280, 320, 8)
    lfm2_cell = (16384, 2048, 1792, 32, 4)
    with_the_rule = {cell: _lowered_for_the_tpu(*cell)
                     for cell in (solar_cell, lfm2_cell)}
    # a block's forward is 3 grouped matmuls and its backward those and
    # their 6 transposes, once outside the loop and once inside: 24
    assert _count(with_the_rule[solar_cell]) == (0, 24)
    # a conditional in the forward and one in the backward, two copies
    # of the first block each
    assert _count(with_the_rule[lfm2_cell]) == (2, 36)
    assert _count(_lowered_for_the_tpu(*lfm2_cell, block_rows=40960)) \
        == (0, 24)
    monkeypatch.setattr(moe, "first_block_rungs",
                        lambda expected, block: (block,))
    for cell, text in with_the_rule.items():
        one_rung = _lowered_for_the_tpu(*cell)
        assert _count(one_rung) == (0, 24)
        assert (one_rung == text) == (cell == solar_cell)


def _conds(jaxpr):
    """Every ``cond`` equation of a jaxpr, however deep."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for v in jax.tree.leaves(
                eqn.params, is_leaf=lambda p: hasattr(p, "eqns")
                or hasattr(p, "jaxpr")):
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _conds(sub)


def test_the_forward_and_the_backward_switch_over_three_branches_each(layer):
    banks = [layer[k][4:8] for k in ("gate", "up", "down")]
    experts, tokens, weights = _routes(48)
    sizes = jnp.bincount(experts, length=4).astype(jnp.int32)

    def loss(x, *b):
        return (moe._grouped_experts(LADDER, x, *b, weights, tokens,
                                     sizes) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, range(4)))(layer["x"], *banks)
    assert [len(e.params["branches"]) for e in _conds(jaxpr.jaxpr)] == [3, 3]
    one = jax.make_jaxpr(jax.grad(
        lambda x: (moe._grouped_experts((48,), x, *banks, weights, tokens,
                                        sizes) ** 2).sum()))(layer["x"])
    assert list(_conds(one.jaxpr)) == []


def test_the_init_program_holds_no_copies_of_the_block(monkeypatch):
    """``SparseExperts`` initialises through one block of the default
    size (nothing of ``init``'s result is kept, and a switch in it was a
    second of the LFM2 cell's cached set-up); ``apply`` switches."""
    monkeypatch.setattr(moe, "first_block_rungs",
                        lambda expected, block: (8, 24, block))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    experts = solar.SparseExperts(16, (0, 4), 4, 12, 0, 1.0, jnp.float32)
    init = jax.make_jaxpr(lambda: experts.init(jax.random.PRNGKey(5), x))()
    assert list(_conds(init.jaxpr)) == []
    params = experts.init(jax.random.PRNGKey(5), x)
    apply = jax.make_jaxpr(lambda p: experts.apply(p, x)[0])(params)
    assert [len(e.params["branches"]) for e in _conds(apply.jaxpr)] == [3]


def test_the_gauges_are_set_from_inside_the_step(layer):
    import horovod_tpu as hvd

    def step(x):
        y, stats = _held({**layer, "x": x}, (4, 4))
        moe.record_held_stats(stats, first=4)
        return y.sum()

    jax.block_until_ready(jax.jit(jax.grad(step))(layer["x"]))
    jax.effects_barrier()
    metrics = hvd.metrics()
    load = {s["labels"]["expert"]: s["value"]
            for s in metrics["hvd_tpu_moe_expert_load"]["samples"]}
    experts = moe.route_top_k(layer["x"], layer["router"], K)[0]
    for e in range(4, 8):
        assert load[str(e)] == float((experts == e).sum())
    assert metrics["hvd_tpu_moe_local_routes"]["samples"][0]["value"] \
        == sum(load[str(e)] for e in range(4, 8))
    assert metrics["hvd_tpu_moe_dropped_tokens"]["samples"][0]["value"] == 0
    # one block of the default size, whole: no rung under 160 rows
    assert metrics["hvd_tpu_moe_worked_rows"]["samples"][0]["value"] == 160
    for name in ("hvd_tpu_moe_dropped_frac", "hvd_tpu_moe_expert_load"):
        assert "top-k" in metrics[name]["help"]
        assert "top-2" not in metrics[name]["help"]


# -- the shares of a whole layer, against the plain reference ---------------

CONFIG = {"hidden_size": 32, "head_dim": 8, "rms_norm_eps": 1e-5,
          "linear_attn_config": {"head_dim": 8, "num_heads": 4,
                                 "short_conv_kernel_size": 4},
          "num_experts_per_tok": 4, "routed_scaling_factor": 1,
          "held_experts_first": 0}


def _columns(kernel, share, shares, width):
    """The share's heads of a (hidden, heads x width) kernel."""
    heads = kernel.shape[-1] // width // shares
    return kernel[..., share * heads * width:(share + 1) * heads * width]


def _rows(kernel, share, shares):
    n = kernel.shape[0] // shares
    return kernel[share * n:(share + 1) * n]


def test_the_heads_shares_add_up_to_the_whole_attention_layers():
    """Heads in 2 shares: each share's GQA (2 of 4 query heads on 1 of 2
    K/V heads) and KDA (2 of 4 heads) module output, ``W_o``'s rows of
    those heads included, add up to the uncut reference's layer."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    gqa = solar.GatedGQA(4, 2, 8, jnp.float32)
    kda = solar.KDA(4, 8, 4, 8, 1e-5, jnp.float32)
    whole_gqa = gqa.init(jax.random.PRNGKey(2), x)["params"]
    whole_kda = kda.init(jax.random.PRNGKey(3), x)["params"]
    with jax.default_matmul_precision("highest"):
        want_gqa = REFERENCE._gqa(x, whole_gqa, CONFIG)
        want_kda = REFERENCE._kda(x, whole_kda, CONFIG)
        got_gqa = got_kda = 0.0
        for share in range(2):
            cols = lambda k: {"kernel": _columns(  # noqa: E731
                whole[k]["kernel"], share, 2, 8)}
            whole = whole_gqa
            part = {**{k: cols(k) for k in ("q", "k", "v", "gate")},
                    "o": {"kernel": _rows(whole["o"]["kernel"], share, 2)}}
            got_gqa += solar.GatedGQA(2, 1, 8, jnp.float32).apply(
                {"params": part}, x)
            whole = whole_kda
            part = {**{k: cols(k) for k in ("q", "k", "v", "f_up", "g_up")},
                    **{k: whole[k] for k in ("f_down", "g_down", "o_norm")},
                    **{"conv_" + k: _columns(whole["conv_" + k], share, 2, 8)
                       for k in "qkv"},
                    "A_log": _rows(whole["A_log"], share, 2),
                    "dt_bias": _rows(whole["dt_bias"], share, 2),
                    "beta": {"kernel": _columns(whole["beta"]["kernel"],
                                                share, 2, 1)},
                    "o": {"kernel": _rows(whole["o"]["kernel"], share, 2)}}
            got_kda += solar.KDA(2, 8, 4, 8, 1e-5, jnp.float32).apply(
                {"params": part}, x)
    np.testing.assert_allclose(got_gqa, want_gqa, atol=3e-6)
    np.testing.assert_allclose(got_kda, want_kda, atol=3e-6)


def test_the_expert_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The share test of the sizing rule: 16 experts in 4 shares of 4.
    Every share computes its experts' routes and the shared expert; the
    routed parts of all shares plus the shared expert counted ONCE are
    what the uncut reference gives for the whole layer."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    whole = solar.SparseExperts(16, (0, 16), 4, 12, 12, 1.0, jnp.float32)
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    with jax.default_matmul_precision("highest"):
        want = REFERENCE._moe(x, params, CONFIG)
        shared = REFERENCE._swiglu(
            x, *(params["shared_" + k]["kernel"]
                 for k in ("gate", "up", "down")))
        routed = 0.0
        for first in (0, 4, 8, 12):
            part = {**params, **{k: params[k][first:first + 4] for k in (
                "experts_gate", "experts_up", "experts_down")}}
            y, _ = solar.SparseExperts(
                16, (first, 4), 4, 12, 12, 1.0, jnp.float32).apply(
                    {"params": part}, x)
            routed += y - shared
            # the reference given the same share gives the same part
            np.testing.assert_allclose(y, REFERENCE._moe(
                x, part, {**CONFIG, "held_experts_first": first}),
                atol=3e-6)
    np.testing.assert_allclose(routed + shared, want, atol=3e-6)


def test_the_loss_held_before_the_backward_is_the_same_loss():
    """``SolarLM.apply(tokens, labels)`` goes through the head's
    ``custom_vjp`` (``_loss_before_the_backward``: what the step tells
    XLA:TPU's scheduler) and the KDA layers' ballast (the identity off a
    TPU): loss and every gradient equal those of the cross-entropy taken
    from ``apply(tokens)``'s logits, which meets neither."""
    model = solar.SolarLM(vocab_size=64, num_layers=2, hidden=32,
                          gqa_layers=(0,), num_heads=2, num_kv_heads=1,
                          head_dim=16, kda_heads=2, kda_head_dim=16,
                          gate_rank=8, num_experts=4, held_experts=(0, 4),
                          top_k=2, expert_dim=16, shared_dim=16,
                          dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 25), 0, 64)
    params = model.init(jax.random.PRNGKey(8), tokens[:, :-1])["params"]

    def from_logits(p):
        logits = model.apply({"params": p}, tokens[:, :-1])
        picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return (jax.nn.logsumexp(logits, -1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(
            lambda p: solar.solar_loss(model, p, tokens))(params)
        want, want_grads = jax.value_and_grad(from_logits)(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


# -- the second router: a sigmoid's scores and a selection bias --------------

LFM2 = Catalog().module("reference", "lfm2")


def test_sigmoid_routing_selects_by_the_biased_scores_and_weighs_by_the_plain(
        layer):
    x, router = layer["x"], layer["router"]
    bias = jax.random.normal(jax.random.PRNGKey(6), (E,)) * 0.3
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
        experts, weights = moe.route_top_k(x, router, K, 1.5, "sigmoid",
                                           bias)
        plain = moe.route_top_k(x, router, K, 1.5, "sigmoid")
    assert experts.shape == weights.shape == (T, K)
    biased = s + np.asarray(bias, np.float64)
    for t in range(T):
        chosen = set(np.argsort(-biased[t])[:K])
        assert set(np.asarray(experts[t])) == chosen
        # the combine weights: the UNBIASED scores of the chosen, over
        # their sum + 1e-6, times the scale
        picked = s[t, np.asarray(experts[t])]
        np.testing.assert_allclose(
            weights[t], 1.5 * picked / (picked.sum() + 1e-6), rtol=2e-6)
    # the bias changed who is chosen for a real share of the tokens
    moved = [set(np.asarray(a)) != set(np.asarray(b))
             for a, b in zip(experts, plain[0])]
    assert 0.2 < np.mean(moved) < 1.0
    # without a bias the top-k is of the scores themselves
    for t in range(T):
        assert set(np.asarray(plain[0][t])) == set(np.argsort(-s[t])[:K])
    # the 1e-6: scores that are all but 0 are not blown up to sum to 1
    cold = moe.route_top_k(jnp.ones((3, D)), jnp.full((D, E), -4.0), K,
                           1.0, "sigmoid")[1]      # every score e^-64
    assert float(cold.max()) < 1e-20 and bool(jnp.isfinite(cold).all())
    # the bias gets no gradient: it reaches the indices alone
    grad = jax.grad(lambda b: moe.route_top_k(
        x, router, K, 1.0, "sigmoid", b)[1].sum())(bias)
    assert float(jnp.abs(grad).max()) == 0.0


@pytest.mark.parametrize("held", [(0, 16), (4, 4)])
def test_the_sigmoid_routers_part_and_its_gradients(layer, held):
    """The held experts' part under the second router against the plain
    reference's dense loop (``benchmark/reference/lfm2.py``)."""
    bias = jax.random.normal(jax.random.PRNGKey(6), (E,)) * 0.3
    config = {"num_experts_per_tok": K, "routed_scaling_factor": 1.0,
              "held_experts_first": held[0]}
    a, b = held[0], held[0] + held[1]
    keys = ("x", "router", "gate", "up", "down")

    def ours(x, router, gate, up, down):
        return moe.held_experts_layer(
            x, router, gate[a:b], up[a:b], down[a:b], E, held, K,
            score="sigmoid", select_bias=bias)[0]

    def plain(x, router, gate, up, down):
        return LFM2._experts(x, {
            "router": router, "select_bias": bias,
            "experts_gate": gate[a:b], "experts_up": up[a:b],
            "experts_down": down[a:b]}, config)

    args = [layer[k] for k in keys]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(*args), plain(*args), atol=2e-6)
        got = jax.grad(lambda *a: (ours(*a) ** 2).sum(), range(5))(*args)
        want = jax.grad(lambda *a: (plain(*a) ** 2).sum(), range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_four_shares_of_8_experts_are_the_uncut_32_expert_layer():
    """The share test of the sizing rule for the sigmoid-bias layer: 32
    experts at top-4 in 4 shares of 8 (``SparseExperts`` with no shared
    expert, as ``models/lfm2.py`` builds it). The parts the four chips
    compute add up to what the uncut plain reference gives for the whole
    layer, and the reference given a share gives that share's part."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    args = (32, 4, 12, 0, 1.0, jnp.float32, "sigmoid", True)
    whole = solar.SparseExperts(args[0], (0, 32), *args[1:])
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    assert set(params) == {"router", "select_bias", "experts_gate",
                           "experts_up", "experts_down"}   # no shared expert
    bias = params["select_bias"]
    assert 0.01 < float(bias.std()) < 0.04
    # the same draw whichever experts a rank holds: a model's start does
    # not depend on how a deployment cuts it
    part = solar.SparseExperts(args[0], (8, 8), *args[1:]).init(
        jax.random.PRNGKey(5), x)["params"]["select_bias"]
    np.testing.assert_array_equal(part, bias)
    config = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
              "held_experts_first": 0}
    with jax.default_matmul_precision("highest"):
        want = LFM2._experts(x, params, config)
        total = 0.0
        for first in (0, 8, 16, 24):
            part = {**params, **{k: params[k][first:first + 8] for k in (
                "experts_gate", "experts_up", "experts_down")}}
            y, stats = solar.SparseExperts(
                args[0], (first, 8), *args[1:]).apply({"params": part}, x)
            np.testing.assert_allclose(y, LFM2._experts(
                x, part, {**config, "held_experts_first": first}), atol=3e-6)
            assert float(stats["dropped_tokens"]) == 0.0
            total += y
    np.testing.assert_allclose(total, want, atol=3e-6)
    assert float(jnp.abs(want).max()) > 0.1


def _route_top_k_before_it_took_a_score(x, router_w, top_k, scale=1.0):
    """``route_top_k`` as it stood while a softmax was the one router."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGH)
    scores, experts = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
    return experts, scores / scores.sum(-1, keepdims=True) * scale


def test_with_the_defaults_the_softmax_path_is_bit_equal_to_before(
        layer, monkeypatch):
    """Solar's cell calls ``held_experts_layer`` with the arguments it
    always did: the same jaxpr, the same outputs and the same gradients,
    to the bit."""
    assert str(jax.make_jaxpr(lambda x, w: moe.route_top_k(x, w, K, 1.5))(
        layer["x"], layer["router"])) == str(jax.make_jaxpr(
            lambda x, w: _route_top_k_before_it_took_a_score(x, w, K, 1.5))(
                layer["x"], layer["router"]))
    keys = ("x", "router", "gate", "up", "down")
    args = [layer[k] for k in keys]

    def run():
        def part(*a):
            return _held(dict(zip(keys, a)), (4, 4))[0]
        return part(*args), jax.grad(lambda *a: (part(*a) ** 2).sum(),
                                     range(5))(*args)

    now, now_grads = run()
    monkeypatch.setattr(
        moe, "route_top_k",
        lambda x, w, k, scale, score, bias:
        _route_top_k_before_it_took_a_score(x, w, k, scale))
    before, before_grads = run()
    assert (np.asarray(now) == np.asarray(before)).all()
    for a, b in zip(now_grads, before_grads):
        assert (np.asarray(a) == np.asarray(b)).all()
