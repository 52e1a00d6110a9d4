"""`reference/fedavg.compare` a leaf at a time.  CPU, run by hand like the
others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_compare_by_leaf.py -q

The comparison reads the globals g0, g1 and gK of program and reference
and holds one leaf's float64 changes at a time.  Its numbers are checked
here against the whole-tree arithmetic it replaced, kept below as the
oracle (every global of every round, a float64 copy of each change), and
its extra host memory against the size of one leaf.
"""

import os
import sys
import tracemalloc

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark.reference import fedavg  # noqa: E402


# -- the oracle: whole trees, every round's global ------------------------------

def _change(states, k):
    return [np.asarray(x, np.float64) - np.asarray(y, np.float64)
            for x, y in zip(jax.tree.leaves(states[k]),
                            jax.tree.leaves(states[0]))]


def _norms(leaves):
    return np.asarray([np.linalg.norm(v.ravel()) for v in leaves])


def oracle(prog_states, prog_loss_r0, ref_states, ref_loss_r0):
    """The comparison over lists [g0, g1, ... gK] of whole trees."""
    k = min(len(prog_states), len(ref_states)) - 1
    rs = ref_states
    out = {"loss_r0": abs(prog_loss_r0 - ref_loss_r0) / abs(ref_loss_r0)}
    g_ref = _norms(_change(rs, 1))
    out["grad1_worst_leaf"] = fedavg._worst_leaf_gap(
        _norms(_change(prog_states, 1)), g_ref)
    keep = g_ref >= 1e-3 * np.median(g_ref)
    out[f"change{k}_worst_leaf"] = fedavg._worst_leaf_gap(
        _norms(_change(prog_states, k)), _norms(_change(rs, k)), keep)
    for j in sorted({1, k}):
        ref_j, prog_j = _change(rs, j), _change(prog_states, j)
        diff = _norms([p - r for p, r in zip(prog_j, ref_j)])
        n_ref = _norms(ref_j)
        out[f"change{j}_diff"] = float(
            np.sqrt(np.sum(diff ** 2)) / np.sqrt(np.sum(n_ref ** 2)))
        out[f"change{j}_median_leaf"] = float(np.median(
            (diff / np.maximum(n_ref, np.median(n_ref)))[keep]))
    return out


# -- seeded trees ----------------------------------------------------------------

SHAPES = {"embed": (97, 16), "blocks": [{"w": (16, 33), "b": (33,)},
                                        {"w": (33, 16), "scale": (16,)}],
          "head": {"kernel": (16, 11)}, "frozen": (7, 5), "quiet": (9,)}


def _tree(rng, like=None, step=1.0):
    """A float32 tree of ``SHAPES``: drawn, or ``like`` moved by a drawn
    step.  ``frozen`` never moves; ``quiet`` moves by rounding only."""
    def leaf(path, shape):
        name = jax.tree_util.keystr(path)
        if like is None:
            return rng.standard_normal(shape).astype(np.float32)
        base = _get(like, path)
        scale = {"['frozen']": 0.0, "['quiet']": 1e-9}.get(name, step)
        return (base + scale * rng.standard_normal(shape)).astype(np.float32)
    return jax.tree_util.tree_map_with_path(
        leaf, SHAPES, is_leaf=lambda v: isinstance(v, tuple))


def _get(tree, path):
    for p in path:
        tree = tree[p.key if hasattr(p, "key") else p.idx]
    return tree


def _rounds(seed, k, spread):
    """(program globals g0..gK, reference globals g0..gK): the same g0,
    the reference's steps drawn, the program's each step off it by a
    relative ``spread``."""
    rng = np.random.default_rng(seed)
    g0 = _tree(rng)
    ref, prog = [g0], [g0]
    for _ in range(k):
        ref.append(_tree(rng, ref[-1], step=0.01))
        moved = jax.tree.map(lambda a, b: a - b, ref[-1], ref[-2])
        prog.append(jax.tree.map(
            lambda p, m: (p + m * (1 + spread * rng.standard_normal(m.shape))
                          ).astype(np.float32), prog[-1], moved))
    # the program moves the quiet leaf where the reference does not
    prog[k] = dict(prog[k], quiet=prog[k]["quiet"] + np.float32(0.5))
    return prog, ref


def _kept(states):
    k = len(states) - 1
    return {0: states[0], 1: states[1], k: states[k]}


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("seed", [0, 2147483659])
def test_compare_gives_the_whole_tree_numbers_exactly(k, seed):
    prog, ref = _rounds(seed, k, spread=0.01)
    got = fedavg.compare(_kept(prog), 2.5,
                         {"states": _kept(ref), "loss_r0": 2.4})
    want = oracle(prog, 2.5, ref, 2.4)
    assert list(got) == list(want)
    assert got == want                       # the same arithmetic: equal
    assert f"change{k}_diff" in got and got["grad1_worst_leaf"] > 0


def test_the_quiet_and_the_frozen_leaf_are_left_out_of_the_change():
    """``keep`` drops a leaf whose first change in the reference is under
    a thousandth of the median leaf's, so the program's moving it does
    not reach the worst leaf; the oracle without ``keep`` would."""
    prog, ref = _rounds(5, 3, spread=0.01)
    got = fedavg.compare(_kept(prog), 1.0, {"states": _kept(ref),
                                            "loss_r0": 1.0})
    norms = fedavg.leaf_norms(_kept(prog), _kept(ref), [1, 3])
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(ref[0])[0]]
    g_ref = norms["ref"][1]
    dropped = {n for n, g in zip(names, g_ref) if g < 1e-3 * np.median(g_ref)}
    assert dropped == {"['frozen']", "['quiet']"}
    assert norms["ref"][3][names.index("['frozen']")] == 0.0
    assert norms["diff"][3][names.index("['quiet']")] > 0.4
    every = fedavg._worst_leaf_gap(norms["prog"][3], norms["ref"][3])
    assert got["change3_worst_leaf"] < 0.1 < every
    assert got["loss_r0"] == 0.0


def test_a_structure_mismatch_is_refused():
    prog, ref = _rounds(1, 1, spread=0.0)
    other = {j: dict(t, extra=np.zeros(3, np.float32))
             for j, t in _kept(prog).items()}
    with pytest.raises(ValueError, match="not laid out as the reference's"):
        fedavg.compare(other, 1.0, {"states": _kept(ref), "loss_r0": 1.0})
    with pytest.raises(ValueError, match="kept the globals of rounds"):
        fedavg.compare({0: prog[0], 1: prog[1]}, 1.0,
                       {"states": {0: ref[0], 1: ref[1], 3: ref[1]},
                        "loss_r0": 1.0})
    # a side without g0 is told which rounds it kept, before any tree is read
    with pytest.raises(ValueError, match="kept the globals of rounds"):
        fedavg.compare({1: prog[1]}, 1.0,
                       {"states": _kept(ref), "loss_r0": 1.0})


def test_the_extra_memory_is_a_leafs_not_a_trees():
    """Twenty leaves: the peak host allocation ``compare`` adds stays under
    four times the largest leaf's float64 size (the whole-tree arithmetic
    held five float64 trees)."""
    rng = np.random.default_rng(3)
    sizes = [40_000 + 1_000 * i for i in range(20)]
    trees = []
    g0 = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    for side in range(2):
        g1 = [(a + 0.01 * rng.standard_normal(a.size)).astype(np.float32)
              for a in g0]
        g3 = [(a + 0.01 * rng.standard_normal(a.size)).astype(np.float32)
              for a in g1]
        trees.append({0: g0, 1: g1, 3: g3})
    prog, ref = trees
    fedavg.compare(prog, 1.0, {"states": ref, "loss_r0": 1.0})   # warm
    leaf64 = max(sizes) * 8
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        got = fedavg.compare(prog, 1.0, {"states": ref, "loss_r0": 1.0})
        peak = tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()
    assert 0 < got["change3_diff"]
    assert peak < 4 * leaf64, (peak, leaf64)
    whole = oracle([g0, prog[1], prog[1], prog[3]], 1.0,
                   [g0, ref[1], ref[1], ref[3]], 1.0)
    assert got == whole
