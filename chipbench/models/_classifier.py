"""What the paper's classifiers on feature rows share: the feature
generator, the initialization, the loss and the correct count.

The generator makes a federation of clients with class-conditional
features at the model's full input width from a seed. Everything a mix
varies is read from its traffic file (``traffic/<name>.json``):

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

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- data

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


# ---------------------------------------------------------------- model

def init_params(shapes, key):
    """Each weight normal / sqrt(fan-in) from its own fold of ``key`` (in
    sorted key order), the bias ``b`` zero."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name == "b":
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        fan_in = math.prod(shape[1:]) if len(shape) == 4 else shape[0]
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) / math.sqrt(fan_in))
    return out


def _ce(logits, y):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))


def _kl(p_logits, q_logits):
    p = jax.nn.log_softmax(p_logits, axis=-1)
    q = jax.nn.log_softmax(q_logits, axis=-1)
    return jnp.mean(jnp.sum(jnp.exp(p) * (p - q), axis=-1))


def mutual_loss(logits, other_logits, y, weight):
    """Eqs. 8-9: (1 - a)·CE(f, y) + a·KL(f ‖ g), g held constant."""
    other = jax.lax.stop_gradient(other_logits)
    return (1.0 - weight) * _ce(logits, y) + weight * _kl(logits, other)


# ---------------------------------------------------------------- evaluation

@partial(jax.jit, static_argnums=(0, 1))
def _evaluate(apply, cfg_json, private, test_x, test_y):
    cfg = json.loads(cfg_json)
    dt = jax.tree_util.tree_leaves(private)[0].dtype
    prec = HIGHEST if dt == jnp.float32 else None

    def one(p, x, y):
        pred = jnp.argmax(apply(cfg, p, x.astype(dt), prec), axis=-1)
        return jnp.sum(pred == y)
    return jax.lax.map(lambda a: one(*a), (private, test_x, test_y))


def correct_counts(apply, cfg, private, test_x, test_y):
    """Per-client count of test examples whose argmax under ``apply`` is
    their label."""
    return np.asarray(_evaluate(apply, json.dumps(cfg, sort_keys=True),
                                private, test_x, test_y))


def run_correct(cfg, acc, test_y):
    """The program's per-client accuracy over its test rows, as a count."""
    return np.rint(np.asarray(acc, np.float64) * test_y.shape[1])
