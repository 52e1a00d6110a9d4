"""The fused attention core (ISSUE 38; a selection and grouped key heads,
ISSUE 40): `models/fused_attention.py`'s Pallas kernels through the
interpreter against the XLA blocks of `causal_blocked_attention`, the rule
that chooses between them, the shape the benchmark's readers find the
kernels by, the counts `wave.dispatch` carries, and the kernels compiled
at the GLM, Keye and Laguna cells' sizes for a described TPU v5e (no chip
is attached: nothing of that runs).
"""

import functools
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import expert_attention, sparse_attention, window_attention
from fedml_tpu.core.pallas_agg import pallas_interpret
from fedml_tpu.models import fused_attention as fa
from fedml_tpu.models import transformer as tr
from fedml_tpu.models.indexed_attention import (IndexedAttention,
                                                IndexedGQAArch, topk_mask)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny", "models",
                    "glm47_flash.json")
GLM = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                  "glm47_flash.json")))
KEYE = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                   "keye_vl2_30b_a3b.json")))
TINY_KEYE = json.load(open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                                        "models", "keye_vl2_30b_a3b.json")))

# (B, T, heads, key heads, dk, dv, kernel block, selection): widths 128
# and 256, equal and not; one block (the diagonal alone) up to four (six
# blocks below it); 1, 2 or 4 query heads a key head; no selection, the
# top few of random scores inside the causal past, or a window of the 40
# latest keys (rows past 168 select nothing in their first key tile, past
# 296 nothing in their first two)
CASES = {
    "two_blocks_w128": (1, 256, 2, 2, 128, 128, 128, None),
    "three_blocks_w256_batch2": (2, 384, 1, 1, 256, 256, 128, None),
    "four_blocks_mixed_widths": (1, 512, 2, 2, 128, 256, 128, None),
    "diagonal_alone": (1, 256, 1, 1, 256, 128, 256, None),
    "selected_g1": (1, 256, 2, 2, 128, 128, 128, "top"),
    "selected_g2_batch2": (2, 384, 4, 2, 128, 128, 128, "top"),
    "selected_g4_mixed_widths": (1, 512, 4, 1, 128, 256, 128, "top"),
    "selected_g4_late_first_key": (1, 512, 4, 1, 128, 128, 128, "window"),
    "grouped_g2_no_selection": (1, 384, 4, 2, 128, 128, 128, None),
}


def _selection(kind, b, t, seed=7):
    """[B, T, T] bool inside the causal past, every row a key at least."""
    causal = np.tril(np.ones((t, t), bool))
    if kind == "top":
        return topk_mask(jax.random.normal(jax.random.key(seed), (b, t, t)),
                         t // 5, causal)
    if kind == "window":
        pos = np.arange(t)
        return jnp.asarray(np.broadcast_to(
            causal & (pos[None, :] > pos[:, None] - 40), (b, t, t)))
    return jnp.asarray(np.broadcast_to(causal, (b, t, t)))    # everything


def _inputs(case):
    b, t, h, kv, dk, dv, block, kind = CASES[case]
    keys = jax.random.split(jax.random.key(len(case)), 4)
    q = jax.random.normal(keys[0], (b, t, h, dk))
    k = jax.random.normal(keys[1], (b, t, kv, dk))
    v = jax.random.normal(keys[2], (b, t, kv, dv))
    w = jax.random.normal(keys[3], (b, t, h, dv))
    return q, k, v, w, None if kind is None else _selection(kind, b, t)


def _all_of(core, q, k, v, w):
    """(out, dq, dk, dv) of ``sum(core(q, k, v) * w)``."""
    def weighted(q, k, v):
        out = core(q, k, v)
        return jnp.sum(out * w), out
    (_, out), grads = jax.value_and_grad(weighted, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


@functools.lru_cache(maxsize=None)
def _both(case):
    """((out, dq, dk, dv) through the kernels, the same through XLA)."""
    block = CASES[case][6]
    q, k, v, w, selected = _inputs(case)
    fused = functools.partial(fa.fused_causal_attention, selected=selected,
                              block=block,
                              interpret=pallas_interpret(fa.KERNEL))
    plain = functools.partial(tr._xla_blocked_attention, block=block // 2,
                              selected=selected)
    return _all_of(fused, q, k, v, w), _all_of(plain, q, k, v, w)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_agree_with_the_xla_blocks(case, what):
    """Float32 in and out; the kernels round the operands of a product to
    bfloat16 as the chip's default precision does, the CPU's XLA path
    does not: they agree to that rounding (3e-3 of the norm on the chip
    against ``highest``, PERF.md section 6), nowhere near a wrong mask,
    scale, block or key head."""
    fused, plain = _both(case)
    i = ["out", "dq", "dk", "dv"].index(what)
    got, want = np.asarray(fused[i], np.float64), np.asarray(plain[i],
                                                             np.float64)
    assert got.shape == want.shape and fused[i].dtype == jnp.float32
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) <= 8e-3 * np.linalg.norm(want)
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()


@pytest.mark.parametrize("g", [1, 2, 4])
def test_a_selection_of_everything_is_the_causal_kernels(g):
    """The selected kernels with every causal key selected compute what
    the plain kernels compute on the key heads repeated: the selection
    masks exactly the diagonal's upper half, and the group sum adds each
    query head's share of a key head's gradients.  Not bit for bit (the
    interpreter's XLA fuses the two bodies apart, and an ``exp`` a unit
    in the last place apart can round a probability to another
    bfloat16), but a hundred times closer than the kernels stand to
    the XLA blocks."""
    b, t, h, d, block = 1, 384, 4, 128, 128
    keys = jax.random.split(jax.random.key(g), 4)
    q, w = (jax.random.normal(x, (b, t, h, d)) for x in keys[:2])
    k, v = (jax.random.normal(x, (b, t, h // g, d)) for x in keys[2:])
    everything = _selection("everything", b, t)
    got = _all_of(lambda q, k, v: fa.fused_causal_attention(
        q, k, v, everything, block=block, interpret=True), q, k, v, w)
    want = _all_of(lambda q, k, v: fa.fused_causal_attention(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), block=block,
        interpret=True), q, k, v, w)
    for x, y in zip(got, want):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.linalg.norm(x - y) <= 1e-4 * np.linalg.norm(y)
        assert np.abs(x - y).max() <= 1e-3 * np.abs(y).max()


def test_rows_see_no_later_key():
    """Changing keys and values after position p leaves the rows up to p
    as they were, bit for bit: in the diagonal block and across blocks."""
    b, t, h, d, block = 1, 256, 1, 128, 128
    keys = jax.random.split(jax.random.key(3), 5)
    q, k, v, k2, v2 = (jax.random.normal(x, (b, t, h, d)) for x in keys)
    run = functools.partial(fa.fused_causal_attention, block=block,
                            interpret=True)
    base = run(q, k, v)
    for p in (70, 128, 200):
        late = jnp.arange(t)[None, :, None, None] > p
        moved = run(q, jnp.where(late, k2, k), jnp.where(late, v2, v))
        assert jnp.array_equal(moved[:, :p + 1], base[:, :p + 1])
        assert not jnp.array_equal(moved[:, p + 1:], base[:, p + 1:])


def test_rows_see_no_key_they_did_not_select():
    """Keys and values a row did not select leave it as it was, bit for
    bit, in tiles where it selected nothing at all (rows past 228 select
    nothing under position 188) as in tiles where it selected some."""
    b, t, h, kv, d, block = 1, 384, 4, 2, 128, 128
    keys = jax.random.split(jax.random.key(4), 5)
    q = jax.random.normal(keys[0], (b, t, h, d))
    k, v, k2, v2 = (jax.random.normal(x, (b, t, kv, d)) for x in keys[1:])
    window = _selection("window", b, t)
    run = functools.partial(fa.fused_causal_attention, selected=window,
                            block=block, interpret=True)
    base = run(q, k, v)
    early = jnp.arange(t)[None, :, None, None] < 188
    moved = run(q, jnp.where(early, k2, k), jnp.where(early, v2, v))
    assert jnp.array_equal(moved[:, 228:], base[:, 228:])
    assert not jnp.array_equal(moved[:, :228], base[:, :228])


# -- the rule ------------------------------------------------------------------

def _qkv(t=512, dk=128, dv=128, dtype=jnp.float32, b=1, h=2, kv=None):
    return (jnp.ones((b, t, h, dk), dtype),
            jnp.ones((b, t, kv or h, dk), dtype),
            jnp.ones((b, t, kv or h, dv), dtype))


def _selected(shapes):
    """A selection's shape for ``_qkv``'s."""
    b, t = shapes[0].shape[:2]
    return jax.ShapeDtypeStruct((b, t, t), jnp.bool_)


# every rule refuses with a selection and grouped key heads as without
REFUSED = {
    "ragged_length": dict(t=520),
    "short_length": dict(t=256),
    "head_width_64": dict(dk=64, dv=64),
    "value_width_192": dict(dv=192),
    "bfloat16": dict(dtype=jnp.bfloat16),
    "head_too_long_for_vmem": dict(t=16384, dk=256, dv=256),
    "selected_ragged_length": dict(t=520, h=4, kv=2, sel=True),
    "selected_head_width_64": dict(dk=64, dv=64, h=4, kv=1, sel=True),
    "selected_bfloat16": dict(dtype=jnp.bfloat16, h=4, kv=2, sel=True),
    "grouped_short_length": dict(t=256, h=4, kv=2),
    "grouped_head_too_long_for_vmem": dict(t=16384, dk=256, dv=256, h=4,
                                           kv=1),
}


def _on_tpu():
    return mock.patch.object(jax, "default_backend", lambda: "tpu")


def test_the_cpu_takes_the_xla_path():
    q, k, v = _qkv()
    assert fa.admits(q, k, v) and not tr.fused_core_fits(q, k, v)
    text = str(jax.make_jaxpr(tr.causal_blocked_attention)(q, k, v))
    assert "pallas_call" not in text
    q, k, v = _qkv(h=4, kv=2)
    selected = jnp.asarray(np.tril(np.ones((1, 512, 512), bool)))
    assert fa.admits(q, k, v, selected)
    assert not tr.fused_core_fits(q, k, v, selected)
    text = str(jax.make_jaxpr(tr.causal_blocked_attention)(
        q, k, v, None, selected))
    assert "pallas_call" not in text


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_shapes_the_kernels_do_not_admit_take_the_xla_path(case):
    """Even on a TPU; and the result is the XLA blocks', bit for bit."""
    kw = dict(REFUSED[case])
    sel = kw.pop("sel", False)
    shapes = jax.eval_shape(lambda: _qkv(**kw))
    chosen = _selected(shapes) if sel else None
    with _on_tpu():
        assert not tr.fused_core_fits(*shapes, chosen)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, s: tr.causal_blocked_attention(q, k, v, 128, s))(
                *shapes, chosen)
    assert "pallas_call" not in str(jaxpr)
    if kw.get("t", 0) <= 1024:
        q, k, v = (jax.random.normal(jax.random.key(i), x.shape, x.dtype)
                   for i, x in enumerate(shapes))
        if sel:
            chosen = _selection("top", *chosen.shape[:2])
        assert jnp.array_equal(
            tr.causal_blocked_attention(q, k, v, 128, chosen),
            tr._xla_blocked_attention(q, k, v, 128, chosen))


def _kernel_calls(jaxpr):
    """(name, operand count) of every `pallas_call` in a jaxpr, nested
    jaxprs included."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], len(eqn.invars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


# (shape keywords, a selection, the kernels' name, operands forward and
# backward)
ADMITTED = {
    "small": (dict(), False, "latent_attention", 3, 6),
    "glm_cell": (dict(t=8192, dk=256, dv=256, h=20), False,
                 "latent_attention", 3, 6),
    "mixed_widths": (dict(t=1024, dk=256, dv=128, b=2), False,
                     "latent_attention", 3, 6),
    "keye_cell": (dict(t=8192, h=32, kv=4), True, "selected_attention", 4,
                  7),
    "selected_ungrouped": (dict(t=1024, b=2), True, "selected_attention",
                           4, 7),
    "grouped_unselected": (dict(t=1024, dk=256, h=4, kv=1), False,
                           "selected_attention", 3, 6),
}


@pytest.mark.parametrize("case", sorted(ADMITTED))
def test_admitted_shapes_take_the_kernels_on_a_tpu(case):
    """One forward and one backward kernel, the plain core's by its name
    and with its three and six operands as before the selection came
    (GLM's program is the parent's), the selected or grouped core's by its
    own name with the selection as one more operand."""
    kw, sel, name, n_fwd, n_bwd = ADMITTED[case]
    shapes = jax.eval_shape(lambda: _qkv(**kw))
    chosen = _selected(shapes) if sel else None
    with _on_tpu():
        assert tr.fused_core_fits(*shapes, chosen)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v, s: jnp.sum(tr.causal_blocked_attention(
                q, k, v, None, s)), (0, 1, 2)))(*shapes, chosen)
    assert sorted(_kernel_calls(jaxpr)) == [(f"{name}_backward", n_bwd),
                                            (f"{name}_forward", n_fwd)]


def _latent_attention(rope=32, nope=96, v_dim=128):
    arch = tr.LatentMoEArch.from_dict({
        **json.load(open(TINY)), "qk_nope_head_dim": nope,
        "qk_rope_head_dim": rope, "v_head_dim": v_dim})
    return arch, tr.LatentAttention(arch, block_size=64)


@pytest.mark.parametrize("where, t, widths, fused", [
    ("cpu", 512, dict(), 0.0),
    ("tpu", 520, dict(), 0.0),
    ("tpu", 512, dict(nope=32, rope=32, v_dim=64), 0.0),
    ("tpu", 512, dict(), 1.0),
], ids=["cpu", "ragged_length", "head_width_64", "admitted"])
def test_latent_attention_counts_what_it_handed_over(where, t, widths,
                                                     fused):
    """``attn_stats/calls`` is [1, 1] only where the kernels take it."""
    arch, layer = _latent_attention(**widths)
    x = jax.random.normal(jax.random.key(1), (1, t, arch.hidden_size))
    params = {"params": layer.init(jax.random.key(0), x[:, :8],
                                   jnp.arange(8))["params"]}
    # the kernels themselves are not this test's: a stand-in of the
    # result's shape, so that nothing is lowered for a chip that is absent
    stand_in = mock.Mock(side_effect=lambda q, k, v, *a, **kw: jnp.zeros(
        v.shape, jnp.float32))
    with mock.patch.object(jax, "default_backend", lambda: where), \
            mock.patch.object(fa, "fused_causal_attention", stand_in):
        _, sown = layer.apply(params, x, jnp.arange(t),
                              mutable=["attn_stats"])
    calls, = jax.tree.leaves(sown["attn_stats"])
    assert calls.tolist() == [1.0, fused]
    assert stand_in.call_count == int(fused)


def _tiny_keye(**kw):
    """The tiny Keye configuration at heads of 128 the kernels admit (and
    the published rotary sections, which add up to such a head's 64)."""
    return IndexedGQAArch.from_dict({
        **TINY_KEYE, "head_dim": 128,
        "rope_scaling": {"mrope_section": [16, 24, 24]}, **kw})


@pytest.mark.parametrize("where, t, fused", [
    ("cpu", 512, 0.0), ("tpu", 520, 0.0), ("tpu", 512, 1.0)],
    ids=["cpu", "ragged_length", "admitted"])
def test_indexed_attention_counts_what_it_handed_over(where, t, fused):
    """The selected core with its grouped key heads: ``[1, 1]`` where the
    kernels take it, and the selection is what they are handed."""
    arch = _tiny_keye()
    layer = IndexedAttention(arch, block_size=128)
    x = jax.random.normal(jax.random.key(1), (1, t, arch.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(t), (3, t))
    params = {"params": layer.init(jax.random.key(0), x[:, :8],
                                   pos[:, :8])["params"]}
    stand_in = mock.Mock(side_effect=lambda q, k, v, *a, **kw: jnp.zeros(
        q.shape, jnp.float32))
    with mock.patch.object(jax, "default_backend", lambda: where), \
            mock.patch.object(fa, "fused_causal_attention", stand_in):
        _, sown = layer.apply(params, x, pos, mutable=["attn_stats"])
    calls, = jax.tree.leaves(sown["attn_stats"])
    assert calls.tolist() == [1.0, fused]
    assert stand_in.call_count == int(fused)
    if fused:
        (q, k, v, selected), _ = stand_in.call_args
        assert q.shape[2] == arch.num_attention_heads
        assert k.shape[2] == v.shape[2] == arch.num_key_value_heads
        assert selected.shape == (1, t, t) and selected.dtype == bool


def _block_kernels(model, t=512):
    """The kernels in a training step's gradient of ``model`` on a
    mocked TPU."""
    tokens = jnp.ones((1, t), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens[:, :8])["params"])
    with _on_tpu():
        return _kernel_calls(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(model.apply({"params": p}, tokens,
                                          train=True))))(params))


def test_a_block_runs_the_forward_kernel_once_a_step():
    """The block's `jax.checkpoint` keeps what the core names (`SAVED`),
    so a training step holds one forward and one backward kernel a
    layer, not a second forward for the backward pass."""
    cfg = {**json.load(open(TINY)), "qk_nope_head_dim": 96,
           "qk_rope_head_dim": 32, "v_head_dim": 128,
           "num_nextn_predict_layers": 0}
    arch = tr.LatentMoEArch.from_dict(cfg)
    found = _block_kernels(tr.TransformerLM(
        vocab_size=cfg["vocab_held"], arch=arch, block_size=64))
    assert sorted(found) == sorted(
        [("latent_attention_forward", 3), ("latent_attention_backward", 6)]
        * arch.num_hidden_layers)


def test_an_indexed_block_runs_the_forward_kernel_once_a_step():
    """The same for the selected core: the block's checkpoint keeps the
    result, the log-sum-exp and the selection, so a step holds one
    selected forward and one selected backward kernel a layer."""
    arch = _tiny_keye()
    found = _block_kernels(tr.TransformerLM(
        vocab_size=TINY_KEYE["vocab_held"], arch=arch, block_size=128))
    assert sorted(found) == sorted(
        [("selected_attention_forward", 4),
         ("selected_attention_backward", 7)] * arch.num_hidden_layers)


# -- what the benchmark's readers find the kernels by ------------------------------

def _kernel_call(kernel, sharding=None, cell="glm"):
    """(the kernel's call, its arguments' shapes at the GLM cell's size,
    the Keye cell's with its selection, or the window layers' of the
    Laguna cell with their window of 512 keys)."""
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
    h, kv, d = {"glm": (20, 20, 256), "keye": (32, 4, 128),
                "laguna": (64, 8, 128)}[cell]
    window = 512 if cell == "laguna" else None
    q, k, row = shape(1, h, 8192, d), shape(1, kv, 8192, d), shape(
        1, h, 1, 8192)
    selected = () if cell != "keye" else (shape(1, 8192, 8192,
                                                dtype=jnp.bool_),)
    if kernel == "forward":
        return (lambda q, k, v, *s: fa._forward(
            q, k, v, *(s or (None,)), fa.BLOCK, False, window),
            (q, k, k) + selected)
    return (lambda q, k, v, lse, delta, do, *s: fa._backward(
        q, k, v, lse, delta, do, *(s or (None,)), fa.BLOCK, False, window),
        (q, k, k, row, row, q) + selected)


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_first_result_is_what_the_reader_groups_by(kernel):
    """Four dimensions, the heads in axis 1 (the log-sum-exp comes
    second): `benchmark.expert_attention.group_of` puts the custom call
    in the ``attention`` group by exactly that."""
    fn, args = _kernel_call(kernel)
    first = jax.eval_shape(fn, *args)[0]
    assert len(first.shape) == 4 and first.shape[1] == 20
    m = dict(GLM["model"], block=GLM["cli"]["attn_block_size"])
    dims = ",".join(str(d) for d in first.shape)
    line = (f"%latent_attention_{kernel}.3 = (f32[{dims}]{{3,2,1,0:T(8,128)}}"
            f", f32[1,20,1,8192]{{3,2,1,0:T(1,128)}}) custom-call(%a, %b), "
            f"custom_call_target=\"tpu_custom_call\"")
    assert expert_attention.group_of(line, m) == "attention"


def _keye_model():
    return dict(KEYE["model"], block=KEYE["cli"]["attn_block_size"],
                batch=KEYE["cli"]["batch_size"])


@pytest.mark.parametrize("heads", [32, 4])
@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_selected_first_result_is_what_the_reader_groups_by(kernel, heads):
    """`benchmark.sparse_attention.group_of` puts a selected kernel's
    custom call in the ``attention`` group by its first result, a mix of
    the 32 query heads as the kernels write it (or of the 4 key heads);
    the int8 selection it reads is no group's."""
    fn, args = _kernel_call(kernel, cell="keye")
    first = jax.eval_shape(fn, *args)[0]
    assert first.shape == (1, 32, 8192, 128)
    line = (f"%selected_attention_{kernel}.2 = (f32[1,{heads},8192,128]"
            f"{{3,2,1,0:T(8,128)}}, f32[1,32,1,8192]{{3,2,1,0:T(1,128)}}) "
            f"custom-call(%q, %k, %v, %s), custom_call_target="
            f"\"tpu_custom_call\"")
    assert sparse_attention.group_of(line, _keye_model()) == "attention"
    convert = ("%convert.7 = s8[1,8192,8192]{2,1,0:T(8,128)(4,1)} "
               "convert(pred[1,8192,8192]{2,1,0} %selected)")
    assert sparse_attention.group_of(convert, _keye_model()) is None


# -- the counts on `wave.dispatch` ---------------------------------------------

def _dispatch_args(tmp_path, argv):
    from fedml_tpu.experiments.main import main
    run_dir = str(tmp_path / "run")
    main(argv + ["--algo", "cross_device", "--client_num_per_round", "2",
                 "--wave_size", "2", "--batch_size", "2", "--comm_round",
                 "1", "--perf", "true", "--log_stdout", "false",
                 "--run_dir", run_dir])
    events = json.load(open(os.path.join(run_dir, "trace.json")))[
        "traceEvents"]
    found = [e["args"] for e in events if e["name"] == "wave.dispatch"]
    assert found
    return found


def test_wave_dispatch_counts_attention_cores(tmp_path):
    """Layers x the client-steps that held a row (the counts of a step
    branched or selected around are zeros), none fused on the CPU."""
    for args in _dispatch_args(tmp_path, [
            "--model", "transformer", "--model_config", TINY, "--dataset",
            "token_shards", "--client_num_in_total", "4"]):
        layers = json.load(open(TINY))
        cores = layers["num_hidden_layers"] + layers[
            "num_nextn_predict_layers"]
        assert 0 < args["attn_calls"] <= cores * args["steps"]
        assert args["attn_calls"] % cores == 0
        assert args["attn_calls_fused"] == 0


def test_wave_dispatch_counts_none_without_attention(tmp_path):
    for args in _dispatch_args(tmp_path, [
            "--model", "resnet56", "--dataset", "cifar10",
            "--client_num_in_total", "2"]):
        assert args["attn_calls"] == 0 and args["attn_calls_fused"] == 0
        # nor pairs for an indexer to select from (ISSUE 39)
        assert args["attn_pairs_causal"] == 0
        assert args["attn_pairs_selected"] == 0


def test_attn_fused_share_reads_those_counts():
    """The benchmark's reader of the two args: a share where cores were
    handed over, nothing where none were (ResNet, the parent)."""
    from benchmark import span_readers
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "attn_fused_share.json")))
    assert spec["reader"] == "benchmark.span_readers:arg_share"

    def share(spans):
        with mock.patch.object(span_readers, "in_window",
                               lambda ctx, name: spans):
            return span_readers.arg_share({}, **spec["args"])
    assert share([{"args": {"attn_calls": 12.0, "attn_calls_fused": 12.0}},
                  {"args": {"attn_calls": 12.0, "attn_calls_fused": 6.0}}
                  ]) == 75.0
    assert share([{"args": {"attn_calls": 0.0, "attn_calls_fused": 0.0}}
                  ]) is None
    assert share([{"args": {"slots": 2}}]) is None


# -- compiled for the chip, at the cell's size ------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", ["glm", "keye", "laguna"])
@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_kernels_compile_for_a_v5e_at_the_cells_size(kernel, cell, one_chip):
    """Mosaic takes the kernels at 20 heads x 8,192 x 256 (GLM), at 32
    query / 4 key heads x 8,192 x 128 with the selection (Keye) and at 64
    query / 8 key heads x 8,192 x 128 under a window of 512 keys (Laguna's
    window layers) within the VMEM they ask for, and the custom call's
    first result is the ``[B, heads, T, width]`` array (what the
    interpreter cannot show)."""
    fn, args = _kernel_call(kernel, one_chip, cell)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1
    if cell == "glm":
        assert "%latent_attention_" in calls[0]
        m = dict(GLM["model"], block=GLM["cli"]["attn_block_size"])
        assert expert_attention.group_of(calls[0], m) == "attention"
    elif cell == "keye":
        assert "%selected_attention_" in calls[0]
        assert sparse_attention.group_of(calls[0], _keye_model()) == \
            "attention"
    else:
        assert "%window_attention_" in calls[0]
        laguna = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                             "laguna_xs2.json")))
        m = window_attention.with_layers(laguna["model"], 1)
        assert window_attention.group_of(calls[0], m) == "window"
        assert sparse_attention.group_of(calls[0], _keye_model()) is None
