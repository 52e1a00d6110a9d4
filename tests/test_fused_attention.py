"""The fused attention core (ISSUE 38): `models/fused_attention.py`'s two
Pallas kernels through the interpreter against the XLA blocks of
`causal_blocked_attention`, the rule that chooses between them, the shape
the benchmark's reader finds the kernels by, the counts `wave.dispatch`
carries, and both kernels compiled at the GLM cell's size for a described
TPU v5e (no chip is attached: nothing of that runs).
"""

import functools
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import expert_attention
from fedml_tpu.core.pallas_agg import pallas_interpret
from fedml_tpu.models import fused_attention as fa
from fedml_tpu.models import transformer as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny", "models",
                    "glm47_flash.json")
GLM = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                  "glm47_flash.json")))

# (B, T, heads, dk, dv, kernel block): widths 128 and 256, equal and not;
# one block (the diagonal alone) up to four (six blocks below it)
CASES = {
    "two_blocks_w128": (1, 256, 2, 128, 128, 128),
    "three_blocks_w256_batch2": (2, 384, 1, 256, 256, 128),
    "four_blocks_mixed_widths": (1, 512, 2, 128, 256, 128),
    "diagonal_alone": (1, 256, 1, 256, 128, 256),
}


@functools.lru_cache(maxsize=None)
def _both(case):
    """((out, dq, dk, dv) through the kernels, the same through XLA)."""
    b, t, h, dk, dv, block = CASES[case]
    keys = jax.random.split(jax.random.key(len(case)), 4)
    q, k = (jax.random.normal(x, (b, t, h, dk)) for x in keys[:2])
    v, w = (jax.random.normal(x, (b, t, h, dv)) for x in keys[2:])
    fused = functools.partial(fa.fused_causal_attention, block=block,
                              interpret=pallas_interpret(fa.KERNEL))
    plain = functools.partial(tr._xla_blocked_attention, block=block // 2)

    def all_of(core):
        def weighted(q, k, v):
            out = core(q, k, v)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(weighted, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out,) + grads
    return all_of(fused), all_of(plain)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_agree_with_the_xla_blocks(case, what):
    """Float32 in and out; the kernels round the operands of a product to
    bfloat16 as the chip's default precision does, the CPU's XLA path
    does not: they agree to that rounding (3e-3 of the norm on the chip
    against ``highest``, PERF.md section 6), nowhere near a wrong mask,
    scale or block."""
    fused, plain = _both(case)
    i = ["out", "dq", "dk", "dv"].index(what)
    got, want = np.asarray(fused[i], np.float64), np.asarray(plain[i],
                                                             np.float64)
    assert got.shape == want.shape and fused[i].dtype == jnp.float32
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) <= 8e-3 * np.linalg.norm(want)
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()


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


# -- the rule ------------------------------------------------------------------

def _qkv(t=512, dk=128, dv=128, dtype=jnp.float32, b=1, h=2):
    return (jnp.ones((b, t, h, dk), dtype), jnp.ones((b, t, h, dk), dtype),
            jnp.ones((b, t, h, dv), dtype))


REFUSED = {
    "ragged_length": dict(t=520),
    "short_length": dict(t=256),
    "head_width_64": dict(dk=64, dv=64),
    "value_width_192": dict(dv=192),
    "bfloat16": dict(dtype=jnp.bfloat16),
    "head_too_long_for_vmem": dict(t=16384, dk=256, dv=256),
}


def _on_tpu():
    return mock.patch.object(jax, "default_backend", lambda: "tpu")


def test_the_cpu_takes_the_xla_path():
    q, k, v = _qkv()
    assert fa.admits(q, k, v) and not tr.fused_core_fits(q, k, v)
    text = str(jax.make_jaxpr(tr.causal_blocked_attention)(q, k, v))
    assert "pallas_call" not in text


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_shapes_the_kernels_do_not_admit_take_the_xla_path(case):
    """Even on a TPU; and the result is the XLA blocks', bit for bit."""
    kw = REFUSED[case]
    shapes = jax.eval_shape(lambda: _qkv(**kw))
    with _on_tpu():
        assert not tr.fused_core_fits(*shapes)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: tr.causal_blocked_attention(q, k, v, 128))(
                *shapes)
    assert "pallas_call" not in str(jaxpr)
    if kw.get("t", 0) <= 1024:
        q, k, v = (jax.random.normal(jax.random.key(i), x.shape, x.dtype)
                   for i, x in enumerate(shapes))
        assert jnp.array_equal(tr.causal_blocked_attention(q, k, v, 128),
                               tr._xla_blocked_attention(q, k, v, 128))


@pytest.mark.parametrize("kw", [dict(), dict(t=8192, dk=256, dv=256, h=20),
                                dict(t=1024, dk=256, dv=128, b=2)],
                         ids=["small", "glm_cell", "mixed_widths"])
def test_admitted_shapes_take_the_kernels_on_a_tpu(kw):
    shapes = jax.eval_shape(lambda: _qkv(**kw))
    with _on_tpu():
        assert tr.fused_core_fits(*shapes)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(tr.causal_blocked_attention(q, k, v)),
            (0, 1, 2)))(*shapes))
    assert text.count("latent_attention_forward") == 1
    assert text.count("latent_attention_backward") == 1


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
    stand_in = mock.Mock(side_effect=lambda q, k, v, **kw: jnp.zeros(
        v.shape, jnp.float32))
    with mock.patch.object(jax, "default_backend", lambda: where), \
            mock.patch.object(fa, "fused_causal_attention", stand_in):
        _, sown = layer.apply(params, x, jnp.arange(t),
                              mutable=["attn_stats"])
    calls, = jax.tree.leaves(sown["attn_stats"])
    assert calls.tolist() == [1.0, fused]
    assert stand_in.call_count == int(fused)


def test_a_block_runs_the_forward_kernel_once_a_step():
    """The block's `jax.checkpoint` keeps what the core names (`SAVED`),
    so a training step holds one forward and one backward kernel a
    layer, not a second forward for the backward pass."""
    cfg = {**json.load(open(TINY)), "qk_nope_head_dim": 96,
           "qk_rope_head_dim": 32, "v_head_dim": 128,
           "num_nextn_predict_layers": 0}
    arch = tr.LatentMoEArch.from_dict(cfg)
    model = tr.TransformerLM(vocab_size=cfg["vocab_held"], arch=arch,
                             block_size=64)
    tokens = jnp.ones((1, 512), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens[:, :8])["params"])
    with _on_tpu():
        text = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(model.apply(
            {"params": p}, tokens, train=True))))(params))
    assert text.count("latent_attention_forward") == arch.num_hidden_layers
    assert text.count("latent_attention_backward") == arch.num_hidden_layers


# -- what the benchmark's reader finds the kernels by ------------------------------

def _kernel_call(kernel, sharding=None):
    """(the kernel's call, its arguments' shapes at the GLM cell's size)."""
    q = jax.ShapeDtypeStruct((1, 20, 8192, 256), jnp.float32,
                             sharding=sharding)
    row = jax.ShapeDtypeStruct((1, 20, 1, 8192), jnp.float32,
                               sharding=sharding)
    if kernel == "forward":
        return (lambda q, k, v: fa._forward(q, k, v, fa.BLOCK, False),
                (q, q, q))
    return (lambda q, k, v, lse, delta, do: fa._backward(
        q, k, v, lse, delta, do, fa.BLOCK, False), (q, q, q, row, row, q))


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


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_kernels_compile_for_a_v5e_at_the_cells_size(kernel, one_chip):
    """Mosaic takes both kernels at 20 heads x 8,192 x 256 within the
    VMEM they ask for, and the custom call's first result is the
    ``[B, heads, T, width]`` array (what the interpreter cannot show)."""
    fn, args = _kernel_call(kernel, one_chip)
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
    m = dict(GLM["model"], block=GLM["cli"]["attn_block_size"])
    assert expert_attention.group_of(calls[0], m) == "attention"
