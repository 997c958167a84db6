"""Traffic generator: a federation of clients with class-conditional
features at the model's full input width, made from a seed.

Everything a mix varies is read from its traffic file (``traffic/<name>.json``):

* ``clients``, ``samples_per_client``, ``train_per_client``: the federation;
* ``classes_per_client``: the shard split of ``repro/data/partition.py``
  (every class cut into shards, shards dealt out in a shuffled order, each
  client taking ``classes_per_client`` consecutive shards of equal size);
* ``features``: ``class_mean_std``, ``noise_std`` and ``norm``. A
  client's example of class c is ``mu_c + noise_std·z``, with ``mu_c``
  drawn N(0, class_mean_std²) per feature and z standard normal, scaled so
  that every feature has zero mean and the same variance over the classes
  (as ScatterNet's channel-wise normalization leaves its features) and an
  example's expected squared norm is ``norm``².

Labels are dealt on the host (a few kilobytes); features are drawn on the
device in one jitted call, client by client, so no temporary of the
federation's size is held beside the result.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def client_labels(traffic, num_classes: int, seed: int):
    """(M, R) labels, train rows first: the shard split, then each client's
    rows shuffled so train and test hold both of its classes."""
    rng = np.random.default_rng(seed)
    M, R = traffic["clients"], traffic["samples_per_client"]
    n = traffic["classes_per_client"]
    per_shard = R // n
    shards_per_class = math.ceil(M * n / num_classes)
    shards = [c for c in range(num_classes) for _ in range(shards_per_class)]
    rng.shuffle(shards)
    labels = np.empty((M, R), np.int32)
    for i in range(M):
        classes = [shards[(i * n + k) % len(shards)] for k in range(n)]
        row = np.repeat(classes, per_shard)
        row = np.concatenate([row, np.full(R - row.size, classes[0])])
        labels[i] = rng.permutation(row)
    return labels


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _features(F, C, n_train, mean_std, noise_std, norm, key, labels):
    M, R = labels.shape
    mu = mean_std * jax.random.normal(jax.random.fold_in(key, 0), (C, F))
    scale = norm / math.sqrt(F * (mean_std ** 2 + noise_std ** 2))

    def one(args):
        i, y = args
        z = jax.random.normal(jax.random.fold_in(key, i + 1), (R, F))
        x = (mu[y] + noise_std * z) * scale
        return x[:n_train], x[n_train:]
    return jax.lax.map(one, (jnp.arange(M), labels))


def make_data(traffic, cfg, key, seed: int):
    """The federation's data on the device: train_x (M, n, F), train_y (M, n),
    test_x (M, R - n, F), test_y (M, R - n)."""
    f = traffic["features"]
    n = traffic["train_per_client"]
    labels = client_labels(traffic, cfg["num_classes"], seed)
    train_x, test_x = _features(cfg["feat_dim"], cfg["num_classes"], n,
                                float(f["class_mean_std"]),
                                float(f["noise_std"]), float(f["norm"]), key,
                                jnp.asarray(labels))
    out = {"train_x": train_x, "train_y": jnp.asarray(labels[:, :n]),
           "test_x": test_x, "test_y": jnp.asarray(labels[:, n:])}
    jax.block_until_ready(out)
    return out
