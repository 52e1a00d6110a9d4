"""Convergence-to-target validation against BASELINE.md accuracy rows.

The algebra tests (test_fedavg_oracle.py) prove the math; these prove
LEARNING: runs that hit the reference's published accuracy targets within
its round budgets (benchmark/README.md:12-14).

* synthetic(0.5, 0.5) LR FedAvg — the EXACT reference generator
  (generate_synthetic.py) — target >60 train acc within 200 rounds;
* MNIST-LR twin (hermetic learnable stand-in, power-law sizes, label skew)
  — reference target >75 train acc within 100+ rounds at the reference
  hyperparameters (1000 clients, 10/round, B=10, SGD lr=0.03, E=1);
* RNN char-LM (the shakespeare trainer flavor) on a deterministic
  next-token task — >90% token accuracy, proving the NLP family learns
  federatedly (mirrors the transformer learning test in
  test_ring_attention.py via the shared identity_lm_data fixture).

All are slow-marked: they run tens-to-hundreds of cohort rounds on CPU.
"""

import pytest

from fedml_tpu.algorithms import FedAvg, FedAvgConfig
from fedml_tpu.data.synthetic import load_synthetic, mnist_learnable_twin
from fedml_tpu.models import LogisticRegression
from fedml_tpu.trainer.workload import ClassificationWorkload


@pytest.mark.slow
def test_synthetic_alpha_beta_lr_to_60():
    """benchmark/README.md:14 — synthetic(α,β) LR FedAvg: >60 train acc,
    30 clients, 10/round, B=10, SGD lr=0.01, E=1, <=200 rounds."""
    data = load_synthetic(alpha=0.5, beta=0.5, num_users=30, batch_size=10,
                          seed=0)
    wl = ClassificationWorkload(
        LogisticRegression(input_dim=60, output_dim=10), num_classes=10,
        grad_clip_norm=None)
    cfg = FedAvgConfig(comm_round=200, client_num_per_round=10, epochs=1,
                       batch_size=10, lr=0.01, frequency_of_the_test=1000,
                       seed=0)
    algo = FedAvg(wl, data, cfg)
    params = algo.run()
    acc = algo.evaluate_global(params)["train_acc"]
    assert acc > 0.60, f"synthetic(0.5,0.5) train acc {acc:.3f} <= 0.60"


@pytest.mark.slow
def test_rnn_charlm_federated_learning_to_target():
    """The RNN family LEARNS federatedly, not just runs (the shakespeare
    trainer flavor): a 2-layer LSTM char-LM on a deterministic
    next-token task (y_t = x_t) must reach >90% token accuracy — the same
    learning-proof pattern as the transformer test
    (test_ring_attention.py)."""
    from conftest import identity_lm_data
    from fedml_tpu.models import RNNOriginalFedAvg
    from fedml_tpu.trainer.workload import NWPWorkload

    model = RNNOriginalFedAvg(vocab_size=12, embedding_dim=8, hidden_size=32)
    data = identity_lm_data()
    cfg = FedAvgConfig(comm_round=100, client_num_per_round=4, epochs=2,
                       batch_size=8, lr=0.5, frequency_of_the_test=99)
    algo = FedAvg(NWPWorkload(model), data, cfg)
    algo.run()
    assert algo.history[-1]["train_acc"] > 0.9, algo.history[-1]


@pytest.mark.slow
def test_mnist_lr_to_75():
    """benchmark/README.md:12 — MNIST LR FedAvg: >75 train acc @ >100
    rounds, 1000 clients, 10/round, B=10, SGD lr=0.03, E=1 (hermetic
    learnable twin standing in for LEAF MNIST; twin noise calibrated so
    the >100-round budget is genuinely needed — 0.54 at round 30,
    0.86 at 119 — instead of saturating at 1.0 within 30 rounds)."""
    data = mnist_learnable_twin(num_clients=1000, batch_size=10, seed=0)
    wl = ClassificationWorkload(
        LogisticRegression(input_dim=784, output_dim=10), num_classes=10,
        grad_clip_norm=None)
    cfg = FedAvgConfig(comm_round=120, client_num_per_round=10, epochs=1,
                       batch_size=10, lr=0.03, frequency_of_the_test=1000,
                       seed=0)
    algo = FedAvg(wl, data, cfg)
    params = algo.run()
    acc = algo.evaluate_global(params)["train_acc"]
    assert acc > 0.75, f"MNIST-LR twin train acc {acc:.3f} <= 0.75"


REF_CURVES = "/root/reference/fedml_api/model/cv/pretrained/CIFAR10/resnet56"


@pytest.mark.skipif(not __import__("os").path.isdir(REF_CURVES),
                    reason="reference curves not mounted")
def test_reference_curve_reader_parses_published_cifar10():
    """The stored resnet56/CIFAR10 trajectory parses and matches
    BASELINE.md's expectations: ~top-1 >90 by the end, monotone learning
    shape (pretrained/CIFAR10/resnet56/train_metrics)."""
    import os
    from fedml_tpu.utils.reference_curves import (curve_is_learning,
                                                  load_reference_curve)
    curve = load_reference_curve(os.path.join(REF_CURVES, "train_metrics"))
    acc = [e["train_accTop1"] for e in curve]
    assert len(acc) > 50
    assert acc[-1] > 90.0
    assert curve_is_learning(acc, min_gain=10.0)


@pytest.mark.slow
def test_noniid_cifar_twin_learning_curve_shape():
    """A non-IID (Dirichlet-partitioned) CIFAR run whose accuracy series
    must show the same qualitative shape as the published reference curve
    (rising tail). Small CNN stands in for resnet56
    so the run fits CPU; the partition/augment path is the real one."""
    import jax
    import flax.linen as nn
    from fedml_tpu.algorithms import FedAvg, FedAvgConfig
    from fedml_tpu.data import load_data
    from fedml_tpu.trainer.workload import ClassificationWorkload
    from fedml_tpu.utils.reference_curves import curve_is_learning

    data = load_data("cifar10", data_dir=None, batch_size=32, client_num=8,
                     partition_method="hetero", partition_alpha=0.5, seed=0)

    class SmallCNN(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.relu(nn.Conv(16, (3, 3), strides=2)(x))
            x = nn.relu(nn.Conv(32, (3, 3), strides=2)(x))
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(x)

    wl = ClassificationWorkload(SmallCNN(), num_classes=10,
                                grad_clip_norm=None)
    cfg = FedAvgConfig(comm_round=30, client_num_per_round=4, epochs=1,
                       batch_size=32, lr=0.05, frequency_of_the_test=5,
                       seed=0)
    algo = FedAvg(wl, data, cfg)
    algo.run()
    accs = [h["train_acc"] for h in algo.history]
    assert curve_is_learning(accs, min_gain=0.05), accs


@pytest.mark.slow
def test_flagship_retention_proxy_on_learnable_cifar_twin():
    """Hermetic proxy of the flagship CIFAR10 row (benchmark/README.md:105
    — centralized 93.19 vs federated 87.12, retention 0.935): on the
    LDA(0.5)-partitioned MULTI-MODE learnable CIFAR twin (modes=4 gives
    each class four prototypes — intra-class variation that makes the
    non-IID gap REAL; the old single-prototype twin saturated at
    fed == cent == 1.0, a ratio that probed nothing), a conv net trained
    with the flagship choreography (10 clients, full participation) must

    * show the gap mid-training (measured: test acc 0.40 at round 10 vs
      centralized 1.00 — the federated run has real work to do), and
    * CLOSE it by the full budget: retention >= 0.94, above the
      published 0.935 ratio (measured 0.992 at pinning time).

    scripts/flagship_accuracy.py runs the full-size resnet56 version of
    this on TPU; this CI tier keeps partition/engine/optimizer real and
    shrinks only the model and round budget."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from fedml_tpu.algorithms.centralized import CentralizedTrainer
    from fedml_tpu.data.synthetic import (FLAGSHIP_TWIN_KWARGS,
                                          cifar_learnable_twin)

    data = cifar_learnable_twin(num_clients=10, samples_per_client=120,
                                partition_alpha=0.5, batch_size=32,
                                seed=0, **FLAGSHIP_TWIN_KWARGS)

    class SmallCNN(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.relu(nn.Conv(16, (3, 3), strides=2)(x))
            x = nn.relu(nn.Conv(32, (3, 3), strides=2)(x))
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(x)

    wl = ClassificationWorkload(SmallCNN(), num_classes=10)
    rounds, epochs = 40, 2
    algo = FedAvg(wl, data, FedAvgConfig(
        comm_round=rounds, client_num_per_round=10, epochs=epochs,
        batch_size=32, lr=0.05, frequency_of_the_test=10, seed=0))
    algo.run()
    fed_acc = algo.history[-1]["test_acc"]
    mid_acc = next((h["test_acc"] for h in algo.history
                    if h["round"] == 10), None)
    assert mid_acc is not None, \
        ("eval cadence no longer covers round 10: "
         f"{[h['round'] for h in algo.history]}")

    trainer = CentralizedTrainer(wl, lr=0.05, epochs_per_call=1)
    pooled = {k: jnp.asarray(v) for k, v in data.train_global.items()}
    params_c = wl.init(jax.random.key(0),
                       jax.tree.map(lambda v: v[0], pooled))
    rng = jax.random.key(1)
    for _ in range(rounds * epochs):
        rng, r = jax.random.split(rng)
        params_c, _ = trainer.local_train(params_c, pooled, r)
    cent_acc = trainer.metrics(
        params_c, {k: jnp.asarray(v)
                   for k, v in data.test_global.items()})["acc"]

    assert cent_acc > 0.90, f"centralized twin too weak: {cent_acc}"
    # the proxy must PROBE the gap: mid-training the federated model is
    # far from centralized (else the task is trivially separable again)
    assert mid_acc < 0.7 * cent_acc, (mid_acc, cent_acc)
    retention = fed_acc / cent_acc
    assert retention >= 0.94, (fed_acc, cent_acc, retention)
