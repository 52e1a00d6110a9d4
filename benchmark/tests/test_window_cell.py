"""The benchmark's tests of the window and full grouped-query expert cell
(``laguna_xs2.silos2``).  CPU, run by hand from the checkout's root with
the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A whole run of the tiny twin (``rehearse_laguna.py``) sound and with its
timed path broken, the reader of the window group
(``benchmark/window_attention.py``) on HLO lines of Laguna's and Keye's
ops, and the required operations against a hand count.
"""

import contextlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark import run, window_attention as wa  # noqa: E402
from benchmark.configs import laguna_xs2  # noqa: E402
import rehearse  # noqa: E402
import rehearse_laguna  # noqa: E402,F401  the tiny cell in rehearse.CELLS
from test_benchmark_unit import FAULTS, REFUSED_BY, SOUND_AT_MOST  # noqa: E402

CONFIG = run.load_json(os.path.join(ROOT, "benchmark", "configs",
                                    "laguna_xs2.json"))
KEYE = run.load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "keye_vl2_30b_a3b.json"))


@pytest.mark.parametrize("fault", ["sound", "state_unchanged",
                                   "half_tokens", "control_bfloat16"])
def test_rehearsal_is_correct_only_when_sound(fault, capfd):
    """The tiny twin through ``run_cell``: correct when sound, refused by
    ``change3_diff`` when its state is returned unchanged, when every
    other target is the pad, and on the program's bfloat16 path."""
    from benchmark.probe import patched
    spec = FAULTS[fault]
    control = fault.startswith("control")
    ctx = (patched(spec[0], spec[1]) if spec and not control
           else contextlib.nullcontext())
    with ctx:
        result = rehearse.rehearse("laguna", seed=2147483659,
                                   extra=spec if control else ())
    capfd.readouterr()
    assert result["correct"] is (fault == "sound"), result["compared"]
    if fault == "sound":
        assert all(row["value"] <= SOUND_AT_MOST
                   for row in result["compared"].values()), result["compared"]
    else:
        row = result["compared"][REFUSED_BY[fault]]
        assert row["value"] > row["limit"], (fault, result["compared"])
    assert result["failed"] == 0 and result["attempted"] >= run.MIN_ROUNDS


L = "{3,2,1,0:T(8,128)}"
LAGUNA_OPS = {
    # the kernels, by name
    f"%window_attention_forward.3 = (f32[1,64,8192,128]{L}, f32[1,64,1,"
    f"8192]{L}) custom-call(f32[1,64,8192,128]{L} %q, f32[1,8,8192,128]{L} "
    f"%k, f32[1,8,8192,128]{L} %v), custom_call_target=\"tpu_custom_call\"":
        "window",
    f"%window_attention_backward.3 = (f32[1,64,8192,128]{L}, f32[1,64,8192,"
    f"128]{L}, f32[1,64,8192,128]{L}) custom-call(%q, %k, %v, %lse, %d, "
    f"%do)": "window",
    # the layer's projections beside the kernels, at the window heads'
    # shapes (the ops PR 44's first reader took, as compiled for a v5e):
    # the query projection's product, the rotary's layout, the output
    # projection's input gradient with the kernels' row sums fused in
    f"%convolution_bitcast_fusion.8 = f32[1,8192,64,128]{{3,1,2,0:T(8,128)}}"
    f" fusion(f32[1,8192,2048]{L} %x, f32[2048,8192]{L} %w), "
    f"kind=kOutput": None,
    f"%maximum_bitcast_fusion = f32[1,64,8192,128]{L} fusion(f32[1,8192,"
    f"64,128]{L} %q), kind=kLoop": None,
    f"%multiply_reduce_fusion.4 = (f32[64,8192]{{1,0:T(8,128)S(1)}}, "
    f"f32[1,64,8192,128]{L}) fusion(%rp, %dy, %o), kind=kOutput": None,
    f"%fusion.22 = f32[1,8,8192,128]{L} fusion(f32[1,64,8192,128]{L} %dk), "
    f"kind=kInput": None,
    # the full layers' grouped kernels and their layouts: 48 heads
    f"%selected_attention_forward.1 = (f32[1,48,8192,128]{L}, f32[1,48,1,"
    f"8192]{L}) custom-call(%q, %k, %v)": None,
    f"%transpose.5 = f32[1,48,8192,128]{L} transpose(f32[1,8192,48,128]{L} "
    f"%q), dimensions={{0,2,1,3}}": None,
    f"%fusion.23 = f32[1,8,8192,128]{L} fusion(f32[1,48,8192,128]{L} %dk), "
    f"kind=kInput": None,
    # keys and values: 8 heads in both kinds of layer
    f"%fusion.24 = f32[1,8192,8,128]{L} fusion(f32[1,8192,1024]{L} %x), "
    f"kind=kLoop": None,
    # the query projection's weight gradient reads the heads, makes a
    # matrix
    f"%convolution.9 = f32[2048,8192]{L} convolution(f32[8192,2048]{L} %x, "
    f"f32[1,8192,64,128]{L} %dq)": None,
    # the experts' tiles, the head, a loop
    f"%fusion.25 = f32[512,512]{L} fusion(f32[512,2048]{L} %x, f32[2048,"
    f"512]{L} %w), kind=kOutput": None,
    f"%fusion.26 = f32[1,8192,12544]{L} fusion(f32[1,8192,2048]{L} %x, "
    f"f32[2048,12544]{L} %w), kind=kOutput": None,
    f"%while.2 = (s32[], f32[1,64,8192,128]{L}) while((s32[], f32[1,64,"
    f"8192,128]{L}) %t), condition=%c, body=%b": None,
}
KEYE_OPS = [
    f"%selected_attention_forward.2 = (f32[1,32,8192,128]{L}, f32[1,32,1,"
    f"8192]{L}) custom-call(%q, %k, %v, %s)",
    f"%fusion.12 = f32[1,8192,32,128]{L} fusion(f32[1,8192,4096]{L} %x)",
    f"%fusion.14 = f32[1,4,8192,128]{L} fusion(f32[1,32,8192,128]{L} %dk)",
    f"%fusion.1 = f32[1,4,8192,3072]{L} fusion(f32[1,8192,4,128]{L} %a)",
]


def test_the_reader_groups_laguna_ops_and_no_keye_op():
    m = wa.with_layers(CONFIG["model"], CONFIG["cli"]["batch_size"])
    assert (m["window_heads"], m["window_layers"]) == (64, 3)
    for line, want in LAGUNA_OPS.items():
        assert wa.group_of(line, m) == want, line
    for line in KEYE_OPS:
        assert wa.group_of(line, m) is None, line


def test_readers_find_nothing_in_a_cell_without_a_window():
    for cell in ("keye_vl2_30b_a3b.silos2", "glm47_flash.silos2",
                 "resnet56_cifar10.silos10"):
        ctx = {"cell": cell, "trace": {}}
        assert wa._model(ctx) is None
        assert wa.window_seconds(ctx) is None
        assert wa.roofline_share(ctx) is None
        assert wa.share_of_wave(ctx) is None
    assert wa._model({"cell": "laguna_xs2.silos2"})["window_layers"] == 3
    assert KEYE["model"]["sliding_window"] is None


def test_required_work_is_a_hand_count():
    """Three passes of 64 heads x 128 x 2 multiply-accumulates a pair in
    the window; q, k, v and the result a pass."""
    m = wa.with_layers(CONFIG["model"], 1)
    pairs = wa.window_pairs(8192, 512)
    assert pairs == 512 * 513 // 2 + (8192 - 512) * 512 == 4_063_488
    flops, nbytes = wa.window_attention_required(m, pairs, 1)
    assert flops == 3 * 2 * 64 * 128 * 2 * pairs
    assert nbytes == 3 * 4 * 8192 * 128 * (2 * 64 + 2 * 8)
    # a round: 2 silos x 2 steps x 3 window layers
    flops, nbytes = wa.window_attention_required(m, 12 * pairs, 12)
    assert flops / 197e12 < nbytes / 819e9      # bytes bound it


def test_forward_macs_are_a_hand_count():
    per_token = laguna_xs2.forward_macs_per_sample(CONFIG, (8192,)) / 8192
    d, hd, t = 2048, 128, 8192
    full = d * hd * (2 * 48 + 2 * 8) + 48 * 2 * hd * (t + 1) / 2
    window = d * hd * (2 * 64 + 2 * 8) + 64 * 2 * hd * 4_063_488 / t
    moe = d * 256 + 3 * d * 512 + 8 * (8 / 256) * 3 * d * 512
    dense = 3 * d * 8192
    want = full + dense + 3 * (window + moe) + full + moe + d * 12_544
    assert per_token == pytest.approx(want, rel=1e-12)
    assert per_token == 390_870_528


def test_the_expert_reader_takes_laguna_tiles_and_no_attention():
    """`expert_attention.group_of` finds Laguna's grouped expert products
    (tiles of 512 rows on 512-wide experts over a 2,048-wide model) and
    forms no attention group without the latent keys."""
    from benchmark import expert_attention as ea
    m = dict(CONFIG["model"], block=CONFIG["cli"]["attn_block_size"],
             batch=CONFIG["cli"]["batch_size"])
    assert not any(k in m for k in ea.LATENT_KEYS)
    for dims, want in (("512,512", "experts"), ("512,2048", "experts"),
                       ("2048,512", "experts"), ("1,64,8192,128", None),
                       ("1,8192,48,128", None), ("8192,512", None)):
        line = f"%fusion.5 = f32[{dims}]{L} fusion(f32[512,2048]{L} %x)"
        assert ea.group_of(line, m) == want, dims
