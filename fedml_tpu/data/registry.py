"""Dataset registry — the TPU-native replacement for the ``load_data`` switch
in every reference entry point (``fedml_experiments/distributed/fedavg/
main_fedavg.py:115-221``: a 100-line if/elif over dataset names).

``load_data(name, data_dir=..., **kw)`` dispatches to the right loader and
returns `FederatedData`.  When ``data_dir`` is None or missing and the
dataset has no on-disk requirement, loaders fall back to hermetic synthetic
twins with the real dataset's shapes so every pipeline runs air-gapped
(``synthetic_ok=False`` disables the fallback for production runs).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional

from .stacking import FederatedData
from .synthetic import load_synthetic, synthetic_federated_dataset

# name -> (real loader kwargs-adapter, synthetic twin)
_REGISTRY: Dict[str, Dict] = {}


def register_dataset(name: str, loader: Callable,
                     synthetic_twin: Optional[Callable] = None,
                     **defaults) -> None:
    _REGISTRY[name] = {"loader": loader, "twin": synthetic_twin,
                       "defaults": defaults}


def dataset_names():
    return sorted(_REGISTRY)


def _accepted_kwargs(fn, kw: Dict) -> Dict:
    """Keep only kwargs ``fn`` can accept (twins and loaders have different
    signatures; a real-loader option must not crash the hermetic path)."""
    import inspect
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return kw
    if any(p.kind is inspect.Parameter.VAR_KEYWORD
           for p in sig.parameters.values()):
        return kw
    return {k: v for k, v in kw.items() if k in sig.parameters}


def load_data(name: str, data_dir: Optional[str] = None,
              synthetic_ok: bool = True, **kw) -> FederatedData:
    if name not in _REGISTRY:
        raise KeyError(f"unknown dataset {name!r}; have {dataset_names()}")
    entry = _REGISTRY[name]
    if data_dir is not None:
        # an explicitly named data_dir that is missing is a user error, not a
        # request for hermetic mode — never silently train on noise
        if not os.path.isdir(data_dir):
            raise FileNotFoundError(
                f"dataset {name!r}: data_dir {data_dir!r} does not exist")
        merged = {**entry["defaults"], **kw}
        accepted = _accepted_kwargs(entry["loader"], merged)
        # twin-only kwargs (e.g. num_clients) are dropped quietly; anything
        # NEITHER callable accepts is a typo and must fail loudly
        dropped = set(merged) - set(accepted)
        twin_ok = set(_accepted_kwargs(entry["twin"], merged)) \
            if entry["twin"] is not None else set()
        unknown = dropped - twin_ok
        if unknown:
            raise TypeError(
                f"dataset {name!r}: unknown option(s) {sorted(unknown)}")
        return entry["loader"](data_dir=data_dir, **accepted)
    if synthetic_ok and entry["twin"] is not None:
        return entry["twin"](**_accepted_kwargs(entry["twin"], kw))
    raise FileNotFoundError(
        f"dataset {name!r}: no data_dir given and synthetic fallback "
        f"disabled/unavailable")


def _register_all() -> None:
    from . import leaf, tff_h5, cifar
    from functools import partial

    img_twin = lambda shape, classes: partial(
        synthetic_federated_dataset, sample_shape=shape, class_num=classes)

    register_dataset("mnist", leaf.load_mnist,
                     img_twin((784,), 10))
    # the CONVERGENCE-grade MNIST stand-in (class prototypes + noise,
    # LEAF power-law sizes): unlike the shape-only noise twin above, a
    # model actually learns on it, so runs that gate on rounds-to-target
    # accuracy (tests/test_convergence.py) can run hermetic
    from .synthetic import mnist_learnable_twin
    register_dataset("mnist_learnable_twin", leaf.load_mnist,
                     mnist_learnable_twin)
    register_dataset("shakespeare", leaf.load_shakespeare_leaf,
                     partial(synthetic_federated_dataset,
                             sample_shape=(80,), sequence_vocab=90,
                             class_num=90))
    register_dataset("synthetic", lambda data_dir=None, **kw:
                     leaf.load_synthetic_leaf(data_dir, **kw),
                     load_synthetic)
    register_dataset("femnist", tff_h5.load_federated_emnist,
                     img_twin((28, 28, 1), 62))
    register_dataset("fed_cifar100", tff_h5.load_fed_cifar100,
                     img_twin((32, 32, 3), 100))
    register_dataset("fed_shakespeare", tff_h5.load_fed_shakespeare,
                     partial(synthetic_federated_dataset,
                             sample_shape=(80,), sequence_vocab=90,
                             class_num=90))
    register_dataset("stackoverflow_nwp", tff_h5.load_stackoverflow_nwp,
                     partial(synthetic_federated_dataset,
                             sample_shape=(20,), sequence_vocab=10004,
                             class_num=10004))
    register_dataset("stackoverflow_lr", tff_h5.load_stackoverflow_lr,
                     partial(synthetic_federated_dataset,
                             sample_shape=(10000,), class_num=500,
                             multilabel=True))
    from . import token_shards
    register_dataset("token_shards", token_shards.load_token_shards,
                     partial(synthetic_federated_dataset,
                             sample_shape=(64,), sequence_vocab=100,
                             class_num=100))
    for ds in ("cifar10", "cifar100", "cinic10"):
        register_dataset(
            ds,
            partial(cifar.load_cifar_partitioned, ds),
            img_twin((32, 32, 3), 100 if ds == "cifar100" else 10),
            client_num=10)

    from . import imagenet
    register_dataset("ilsvrc2012", imagenet.load_imagenet,
                     img_twin((224, 224, 3), 1000))
    # per-name mapping-csv defaults (Landmarks/data_loader.py docstring:
    # data_user_dict/gld{23k,160k}_user_dict_train.csv under the data root)
    register_dataset(
        "gld23k", imagenet.load_landmarks, img_twin((224, 224, 3), 203),
        mapping_csv="data_user_dict/gld23k_user_dict_train.csv")
    register_dataset(
        "gld160k", imagenet.load_landmarks, img_twin((224, 224, 3), 2028),
        mapping_csv="data_user_dict/gld160k_user_dict_train.csv")


_register_all()
