"""Quickstart: the paper's pipeline in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py

1. synthesize a non-IID federated dataset (Dirichlet label skew + latent
   style groups),
2. compute each client's distribution summary three ways — P(y), P(X|y),
   and the paper's coreset+encoder summary (§4.1),
3. cluster the summaries (K-means, §4.2) and check which summary recovers
   the true heterogeneity structure,
4. run one HACCS-style selection round.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SelectionConfig, encoder_summary, kmeans,
                        label_distribution, pxy_histogram, select_devices)
from repro.data.synthetic import FederatedDataset, small_spec
from repro.models.cnn import CNNConfig, build_cnn, cnn_apply
from repro.utils.cache import use_compile_cache

use_compile_cache()

# alpha=50 -> near-IID labels: only FEATURE heterogeneity separates clients,
# the regime where the paper shows P(y) fails and the encoder summary wins
spec = small_spec(num_clients=40, num_classes=8, side=12, avg_samples=64,
                  num_styles=4, alpha=50.0)
data = FederatedDataset(spec, seed=0)
print(f"dataset: {spec.num_clients} clients, {spec.num_classes} classes, "
      f"{spec.num_styles} latent style groups")

enc = build_cnn(CNNConfig(in_channels=1, feature_dim=32), jax.random.PRNGKey(1))
enc_fn = jax.jit(lambda x: cnn_apply(enc, x))

summaries = {"py": [], "encoder": []}
t0 = time.time()
for c in range(spec.num_clients):
    feats, labels, valid = (jnp.asarray(a) for a in data.client_data(c))
    summaries["py"].append(np.asarray(
        label_distribution(labels, valid, spec.num_classes)))
    summaries["encoder"].append(np.asarray(encoder_summary(
        feats, labels, valid, enc_fn, spec.num_classes, coreset_k=32,
        key=jax.random.PRNGKey(c))))
print(f"summaries computed in {time.time() - t0:.1f}s "
      f"(P(y) dim={summaries['py'][0].size}, "
      f"encoder dim={summaries['encoder'][0].size})")


def purity(assign):
    truth = data.true_groups()
    return sum(np.bincount(truth[assign == c]).max()
               for c in range(spec.num_styles)
               if (assign == c).any()) / spec.num_clients


for name, S in summaries.items():
    res = kmeans(jnp.asarray(np.stack(S), jnp.float32), spec.num_styles,
                 jax.random.PRNGKey(0))
    print(f"kmeans on {name:8s}: {int(res.iterations)} iters, "
          f"group purity {purity(np.asarray(res.assignment)):.2f}")

res = kmeans(jnp.asarray(np.stack(summaries["encoder"]), jnp.float32),
             spec.num_styles, jax.random.PRNGKey(0))
speeds = np.random.RandomState(0).lognormal(0, 0.8, spec.num_clients)
sel = select_devices(np.asarray(res.assignment), spec.num_styles, speeds,
                     np.ones(spec.num_clients, bool),
                     SelectionConfig(8, "haccs"), np.random.default_rng(0))
print(f"selected devices this round: {sel.tolist()} "
      f"(clusters {sorted(set(np.asarray(res.assignment)[sel].tolist()))})")
