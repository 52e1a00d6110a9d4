"""`utils.journal.tree_crc` is one value whatever the tree's size: a
GB-size global is read in pieces by several threads and the pieces' crc32s
combined (ISSUE 38: the CRC runs beside the next round's wave program and
must not outlast it), a small tree in one chain as before."""

import zlib

import numpy as np
import pytest

from fedml_tpu.utils import journal


def _chained(leaves) -> int:
    crc = 0
    for leaf in leaves:
        crc = zlib.crc32(np.ascontiguousarray(leaf).reshape(-1)
                         .view(np.uint8), crc)
    return crc


@pytest.mark.parametrize("len1, len2", [
    (0, 0), (1, 0), (0, 5), (3, 1), (1000, 77), (12345, 1 << 20),
    (7, (1 << 24) + 3)])
def test_crc32_combine_is_the_crc_of_the_concatenation(len1, len2):
    rng = np.random.default_rng(len1 + len2)
    a, b = (rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (len1, len2))
    assert journal.crc32_combine(zlib.crc32(a), zlib.crc32(b),
                                 len(b)) == zlib.crc32(a + b)


@pytest.mark.parametrize("piece", [1 << 10, 4099, 1 << 14])
def test_a_tree_read_in_pieces_has_the_chained_value(piece, monkeypatch):
    """Pieces that end inside leaves, leaves shorter than a piece, an
    empty leaf and a non-contiguous one."""
    rng = np.random.default_rng(piece)
    tree = {"a": rng.standard_normal((300, 50)).astype(np.float32),
            "b": rng.standard_normal((17,)).astype(np.float32),
            "c": {"d": rng.standard_normal((41, 43)).astype(np.float64).T,
                  "e": np.zeros((0,), np.float32),
                  "f": rng.integers(0, 9, (5000,)).astype(np.int32)}}
    want = _chained([tree["a"], tree["b"], tree["c"]["d"], tree["c"]["e"],
                     tree["c"]["f"]])
    assert journal.tree_crc(tree) == want       # small: one chain
    monkeypatch.setattr(journal, "_CRC_PIECE", piece)
    assert sum(x.nbytes for x in (tree["a"], tree["c"]["d"])) > 4 * piece
    assert journal.tree_crc(tree) == want       # in pieces, by threads
