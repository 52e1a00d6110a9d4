"""The global's CRC, computed on the device (`core.global_crc`).

`TreeCrc` must give exactly `utils.journal.tree_crc` of the tree fetched
to the host: zlib's crc32 over the leaves' C-order bytes.  Held here for
both ways it computes the rows' CRCs (the `jnp` products, and the Pallas
kernel through the interpreter): (a) leaves of every dtype the engine
carries and of the awkward sizes (0 to 5 bytes, ragged tails, rows that
are no whole number of 128 words or of a chunk, an expert-shaped 3-D
leaf, a column-major matrix read in place, a tree on the host's threaded
branch, a whole tiny language-model global); (b) the cross-device engine
ledgers that value for every round, and in a run where no reader needs
the global on the host, no leaf of it is copied there.
"""

import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.cross_device import CrossDevice, CrossDeviceConfig
from fedml_tpu.core import global_crc
from fedml_tpu.core.global_crc import TreeCrc, zeros_crc
from fedml_tpu.data import load_data
from fedml_tpu.experiments.models import create_workload, sample_shape_of
from fedml_tpu.obs.perf import PerfRecorder
from fedml_tpu.utils import journal
from fedml_tpu.utils.journal import tree_crc

PATHS = {"jnp": False, "kernel": True}


def _rng(seed=0):
    return np.random.default_rng(seed)


def _words(shape, seed=0):
    return jnp.asarray(_rng(seed).integers(-2**31, 2**31, shape,
                                           dtype=np.int64).astype(np.int32))


def _floats(shape, seed=0):
    return jnp.asarray(_rng(seed).standard_normal(shape).astype(np.float32))


def _bytes(n, seed=0):
    return jnp.asarray(_rng(seed).integers(0, 256, n, dtype=np.uint8))


def _lm_global():
    """A whole tiny GLM-shaped global (latent attention, experts, the
    next-token module), as the engine holds it."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    workload = create_workload(
        "transformer", "token_shards", 100, (32,),
        model_config=os.path.join(root, "benchmark", "tests", "tiny",
                                  "models", "glm47_flash.json"))
    sample = {"x": jnp.zeros((2, 32), jnp.int32),
              "y": jnp.zeros((2, 32), jnp.int32),
              "mask": jnp.ones((2,), jnp.float32)}
    return workload.init(jax.random.key(0), sample)


TREES = {
    "float32": lambda: {"w": _floats((5, 7)), "b": _floats((7,), 1)},
    "bfloat16": lambda: [_floats((3, 5)).astype(jnp.bfloat16),
                         _floats((9,), 1).astype(jnp.bfloat16)],
    "int32": lambda: [_words((129, 3)), _words((2,), 1)],
    "uint8": lambda: [_bytes(6), _bytes(11, 1)],
    # leaves of 0, 1, 3, 4, 5 and 4,097 bytes, in one tree
    "odd_sizes": lambda: [_bytes(0), _bytes(1, 1), _bytes(3, 2),
                          _words((1,), 3), _bytes(5, 4), _bytes(4097, 5)],
    "empty": lambda: [jnp.zeros((0, 4), jnp.float32)],
    # read in place: 300 rows of 1,000 words (no whole number of 128
    # words, nor of a chunk), between packed leaves
    "rows_in_place": lambda: [_floats((17,)), _floats((300, 1000), 1),
                              _bytes(7, 2), _floats((520, 600), 3)],
    # an expert-shaped leaf: experts x rows x columns, read in place
    "experts": lambda: [_floats((4, 96, 768)), _floats((4, 768, 96), 1)],
    "mixed_dtypes": lambda: [_floats((3, 300, 350)),
                             _floats((7,), 1).astype(jnp.bfloat16),
                             _bytes(5, 2), _words((600, 520), 3),
                             jnp.asarray([True, False, True])],
    # over 4 x `_CRC_PIECE` bytes: `tree_crc` takes its threaded branch
    "host_threaded": lambda: [_words((16, 1024, 1024)), _bytes(9, 1)],
    "lm_global": _lm_global,
}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("case", sorted(TREES))
def test_device_crc_is_tree_crc_of_the_host_copy(case, path):
    tree = TREES[case]()
    if case == "host_threaded":
        assert sum(x.nbytes for x in jax.tree.leaves(tree)) \
            >= 4 * journal._CRC_PIECE
    assert TreeCrc(PATHS[path])(tree) == tree_crc(jax.device_get(tree))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_column_major_matrix_is_read_in_place(path, monkeypatch):
    """A matrix that lies a column after another (the chip's layout of
    one whose rows are no whole number of 128 lanes) is read as its
    transpose, each row of that a column of the message's words: the
    value is the same.  The CPU lays every array row-major, so the
    layout is declared here."""
    monkeypatch.setattr(global_crc, "_column_major",
                        lambda leaf: leaf.ndim == 2)
    tree = [_floats((600, 700)), _floats((1300, 257), 1),
            _floats((2, 300, 600), 2)]
    units = global_crc._plan(tree)
    assert [u.transposed for u in units] == [True, True, False]
    assert TreeCrc(PATHS[path])(tree) == tree_crc(jax.device_get(tree))


@pytest.mark.parametrize("n", [0, 1, 3, 4, 5, 4097, 1 << 20])
def test_zeros_crc_is_zlibs(n):
    assert zeros_crc(n) == zlib.crc32(bytes(n))


def test_a_program_is_built_once_a_kind_of_tree():
    crc = TreeCrc(False)
    a, b = [_floats((5, 7))], [_floats((5, 7), 1)]
    crc(a)
    assert crc(b) == tree_crc(jax.device_get(b))
    assert len(crc._programs) == 1
    crc([_floats((5, 8))])
    assert len(crc._programs) == 2


# ---------------------------------------------------------------------------
# (b) the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data():
    return load_data("mnist", data_dir=None, batch_size=4, num_clients=16,
                     seed=0)


@pytest.fixture(scope="module")
def workload(data):
    return create_workload("lr", "mnist", data.class_num,
                           sample_shape_of(data))


def _cfg(**kw):
    base = dict(comm_round=3, client_num_per_round=8, epochs=1,
                batch_size=4, wave_size=3, seed=0, frequency_of_the_test=10)
    base.update(kw)
    return CrossDeviceConfig(**base)


def _run(tmp_path, workload, data, spy=None):
    """A perf-ledgered run; ``spy`` sees each round's new global as the
    engine hands it to `_start_crc`."""
    path = tmp_path / "perf.jsonl"
    perf = PerfRecorder(str(path))
    try:
        eng = CrossDevice(workload, data, _cfg(), perf=perf)
        if spy is not None:
            start = eng._start_crc

            def start_crc(params):
                spy(params)
                return start(params)
            eng._start_crc = start_crc
        eng.run()
    finally:
        perf.close()
    return [json.loads(line)["global_crc"] for line in open(path)]


def test_every_rounds_ledgered_crc_is_tree_crc_of_its_global(
        workload, data, tmp_path):
    host = []
    ledgered = _run(tmp_path, workload, data,
                    spy=lambda p: host.append(tree_crc(jax.device_get(p))))
    assert len(ledgered) == 3 and len(set(ledgered)) == 3
    assert ledgered == host


def test_no_leaf_of_the_global_goes_to_the_host(workload, data, tmp_path,
                                                monkeypatch):
    """No health sketch and no poison seam: every round's global stays
    on the device, the CRC too; one uint32 a round crosses.  Counted:
    every way the engine had of taking a global to the host (a leaf's
    asynchronous copy, `jax.device_get`, `tree_crc` over host bytes)."""
    held, moved = [], []

    def is_global(x):
        return any(x is leaf for leaf in held)

    def spy(params):
        held.extend(jax.tree.leaves(params))

    array = type(jnp.zeros(1))
    copy_async, get, crc = (array.copy_to_host_async, jax.device_get,
                            journal.tree_crc)

    def counting_copy(self):
        if is_global(self):
            moved.append("copy_to_host_async")
        return copy_async(self)

    def counting(name, real):
        def call(tree):
            if any(is_global(x) for x in jax.tree.leaves(tree)):
                moved.append(name)
            return real(tree)
        return call

    monkeypatch.setattr(array, "copy_to_host_async", counting_copy)
    monkeypatch.setattr(jax, "device_get", counting("device_get", get))
    monkeypatch.setattr(journal, "tree_crc", counting("tree_crc", crc))
    ledgered = _run(tmp_path, workload, data, spy=spy)
    assert len(ledgered) == 3 and all(isinstance(c, int) for c in ledgered)
    assert len(held) == 3 * 2        # lr: a weight and a bias a round
    assert moved == []
    # the counters do see a held leaf go
    held[0].copy_to_host_async()
    jax.device_get(held[1])
    journal.tree_crc(held[2:4])
    assert set(moved) == {"copy_to_host_async", "device_get", "tree_crc"}
