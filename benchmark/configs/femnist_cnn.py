"""Plain reference model for ``femnist_cnn``: the CNN of Reddi et al. 2020
("Adaptive Federated Optimization", table 4; FedML ``CNN_DropOut``): two 3x3
valid convolutions (32, 64), 2x2 max-pool, dropout 0.25, dense 128, dropout
0.5, dense 62.  1,206,590 parameters.  Written from the paper's table in
flax.linen, in float32; nothing of ``fedml_tpu`` is imported.

The submodules keep flax's automatic names (``Conv_0`` ... ``Dense_1``,
``Dropout_0/1``): flax derives each parameter's initial value and each
dropout mask from the module path, so equal paths are what make the
reference start from, and drop, the same units as the system under test.
"""

import flax.linen as nn

from benchmark import datagen


class Model(nn.Module):
    classes: int = 62

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Conv(32, (3, 3), padding="VALID")(x))
        x = nn.relu(nn.Conv(64, (3, 3), padding="VALID")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Dropout(0.25, deterministic=not train)(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        return nn.Dense(self.classes)(x)


def build_model(config: dict) -> nn.Module:
    return Model(classes=config["model"]["classes"])


def train_clients(arrays: dict, config: dict, program_seed: int):
    """Per-client (x, y) of the training split, in population order."""
    return datagen.femnist_clients(arrays, "train")
