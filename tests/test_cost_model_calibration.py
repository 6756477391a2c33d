"""Cost-model calibration: predicted strategy ranking vs MEASURED step
times (VERDICT r2 #6 — turn the advisory ranking into evidence).

The model's times are explicitly "order-of-magnitude for ranking"
(``strategy/cost_model.py``); these tests check the *ranking* claim
against wall-clock measurements of real compiled steps on the virtual
8-device CPU mesh, for a sparse-heavy and a dense workload:

* sparse-heavy — the Parallax argument: builders that densify the
  embedding gradient (AllReduce family) must rank *and measure* slower
  than sparse-PS builders; Kendall tau between predicted and measured
  orderings must be positive.
* dense — all ring lowerings move the same volume, so the model predicts
  near-ties; the check is consistency (the measured-fastest builder's
  predicted time within a small factor of the predicted-fastest), not a
  strict order over ties.

Calibration status recorded here: the RANKING is validated on the CPU
mesh; the absolute times (ICI_BANDWIDTH / COLLECTIVE_ALPHA) remain
hardware-uncalibrated — one real chip cannot measure a cross-chip
collective.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.autodist import AutoDist, _reset_default_autodist_for_testing
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import (
    AllReduce,
    Parallax,
    PartitionedAR,
    PS,
    PSLoadBalancing,
)
from autodist_tpu.strategy.cost_model import estimate_cost


@pytest.fixture(autouse=True)
def _reset():
    _reset_default_autodist_for_testing()


def _spec8():
    return ResourceSpec(resource_info={
        "nodes": [{"address": "127.0.0.1", "chips": 8, "chief": True}]})


def _measure(builder, params, loss_fn, batch, sparse_vars=(), steps=12):
    """Wall-clock step time through the real session path."""
    _reset_default_autodist_for_testing()
    ad = AutoDist(strategy_builder=builder)
    with ad.scope():
        ad.capture(params=params, optimizer=optax.sgd(0.1),
                   loss_fn=loss_fn, sparse_vars=sparse_vars)
    sess = ad.create_distributed_session()
    placed = sess.place_batch(batch)
    for _ in range(3):
        sess.run(placed)
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            sess.run(placed, sync=False)
        float(np.asarray(sess.run(placed)["loss"]))
        reps.append((time.perf_counter() - t0) / (steps + 1))
    return min(reps)   # min over repeats: robust to host noise


def _kendall_tau(a, b):
    """Plain O(n^2) Kendall tau between two equal-length rankings."""
    n = len(a)
    concordant = discordant = 0
    for i in range(n):
        for j in range(i + 1, n):
            s = (a[i] - a[j]) * (b[i] - b[j])
            if s > 0:
                concordant += 1
            elif s < 0:
                discordant += 1
    pairs = n * (n - 1) / 2
    return (concordant - discordant) / pairs


def test_sparse_workload_rank_agreement():
    """Predicted ordering matches measured for the workload where costs
    genuinely differ (dense-vs-sparse embedding sync)."""
    vocab, dim = 200_000, 32
    rng = np.random.RandomState(0)
    params = {
        "emb": {"table": jnp.asarray(rng.randn(vocab, dim) * 0.01,
                                     jnp.float32)},
        "head": {"w": jnp.asarray(rng.randn(dim, 1) * 0.1, jnp.float32)},
    }
    batch = {
        "ids": rng.randint(0, vocab, (256,)).astype(np.int32),
        "y": rng.randn(256).astype(np.float32),
    }

    def loss_fn(p, b):
        rows = jnp.take(p["emb"]["table"], b["ids"], axis=0)
        pred = (rows @ p["head"]["w"])[:, 0]
        return jnp.mean((pred - b["y"]) ** 2)

    builders = [AllReduce(), PartitionedAR(), Parallax(), PSLoadBalancing()]
    spec = _spec8()
    gi = GraphItem(params, sparse_vars=["emb/table"])
    predicted = [estimate_cost(b.build(gi, spec), gi, spec,
                               sparse_rows_hint=256).time_s
                 for b in builders]
    measured = [_measure(b, params, loss_fn, batch,
                         sparse_vars=("emb/table",)) for b in builders]

    # The headline claim: sparse-aware builders beat gradient-densifying
    # ones in BOTH predicted and measured orderings...
    for sparse_aware in (2, 3):          # Parallax, PSLoadBalancing
        for densifying in (0, 1):        # AllReduce, PartitionedAR
            assert predicted[sparse_aware] < predicted[densifying]
            assert measured[sparse_aware] < measured[densifying], (
                builders[sparse_aware], measured)
    # ...and the full orderings correlate beyond what the pairwise
    # asserts already imply (those guarantee tau >= 1/3).
    tau = _kendall_tau(predicted, measured)
    assert tau >= 0.5, (predicted, measured, tau)


def test_dense_workload_prediction_consistency():
    """Dense models: every ring lowering moves the same bytes, so the
    model predicts near-ties — assert it does NOT strongly misorder:
    the measured-fastest builder's predicted time is within 2x of the
    predicted-fastest (ties are fine, contradictions are not)."""
    rng = np.random.RandomState(1)
    params = {
        "l1": {"w": jnp.asarray(rng.randn(512, 512) * 0.05, jnp.float32)},
        "l2": {"w": jnp.asarray(rng.randn(512, 512) * 0.05, jnp.float32)},
        "out": {"w": jnp.asarray(rng.randn(512, 1) * 0.1, jnp.float32)},
    }
    batch = {"x": rng.randn(128, 512).astype(np.float32),
             "y": rng.randn(128).astype(np.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["l1"]["w"])
        h = jnp.tanh(h @ p["l2"]["w"])
        return jnp.mean(((h @ p["out"]["w"])[:, 0] - b["y"]) ** 2)

    builders = [AllReduce(), PS(), PSLoadBalancing(), PartitionedAR()]
    spec = _spec8()
    gi = GraphItem(params)
    predicted = [estimate_cost(b.build(gi, spec), gi, spec).time_s
                 for b in builders]
    measured = [_measure(b, params, loss_fn, batch) for b in builders]

    fastest_measured = int(np.argmin(measured))
    assert predicted[fastest_measured] <= 2.0 * min(predicted), (
        predicted, measured)
