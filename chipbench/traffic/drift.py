"""Traffic kind ``drift``: clients whose label distribution switches
between two states, each round a fixed share of the fleet.

A mix (``traffic/<mix>.json`` with ``"kind": "drift"``) gives
``drift_share``, ``available_share``, ``plan_rounds`` and ``warm_rounds``.
Everything the timed rounds consume is made here from ``--seed`` in
set-up, so the window only hands the program inputs that already exist:

* each client has two label distributions, P(y) states 0 and 1 (two
  Dirichlet(alpha) draws whose symmetric KL is far past the refresh
  threshold);
* every round, ``round(drift_share * N)`` clients switch state.  Those
  clients, and only those, are stale: a client that keeps its state
  reports the very P(y) the registry stored for it;
* where the server computes summaries, every client's images are made for
  both states (class prototypes plus a latent style group, as in
  ``src/repro/data/synthetic.py``) on the device, in fixed-size jitted
  chunks, and kept on the host;
* where clients upload summaries, every client's upload is made for both
  states at the encoder summary's shape: per-label means of a
  min(k, size)-sample coreset (class embedding + style embedding + noise),
  zero for absent labels, then P(y);
* each round's availability mask is drawn in set-up; speeds are fixed.

Client sizes are lognormal quantiles with the source's mean and standard
deviation, dealt out in a seeded order.  With ``size_levels`` L in the
configuration the quantiles are taken at L levels, N / L clients each, and
every round switches the same number of clients at the same levels: each
round's stale set then holds the same sizes, so every seed and every round
asks the same work of the server, and the warm rounds dispatch every shape
the window will.
"""
from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

# the registry's refresh test is sym-KL > refresh_kl; the two states of a
# client are kept at least this many times that far apart
KL_MARGIN = 4.0
IMAGE_CHUNK = 32768      # images made per jitted call


def stream(seed: int, tag: int) -> np.random.Generator:
    """An independent numpy stream per (seed, tag), for a seed of any size."""
    return np.random.default_rng([int(seed), tag])


def sym_kl_rows(p: np.ndarray, q: np.ndarray, eps: float = 1e-9):
    p = np.asarray(p, np.float64) + eps
    q = np.asarray(q, np.float64) + eps
    p = p / p.sum(-1, keepdims=True)
    q = q / q.sum(-1, keepdims=True)
    return 0.5 * (np.sum(p * np.log(p / q), -1) + np.sum(q * np.log(q / p), -1))


def quantile_sizes(cfg: dict) -> np.ndarray:
    """The sorted multiset of client sizes: quantiles of the lognormal with
    the configuration's mean and standard deviation, clipped, at
    ``size_levels`` levels (N / levels clients each) or at N."""
    n = cfg["num_clients"]
    levels = cfg.get("size_levels", n)
    if n % levels:
        raise ValueError(f"{n} clients do not split into {levels} levels")
    mean, sd = cfg["samples_mean"], cfg["samples_sd"]
    sigma2 = math.log(1.0 + (sd / mean) ** 2)
    nd = statistics.NormalDist(math.log(mean) - sigma2 / 2, math.sqrt(sigma2))
    q = np.exp([nd.inv_cdf((i + 0.5) / levels) for i in range(levels)])
    q = np.clip(np.round(q).astype(np.int64), cfg["min_samples"],
                cfg["max_samples"])
    return np.repeat(q, n // levels)


def draw_categorical(rng, probs: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """One draw from ``probs[rows[j]]`` for every j, vectorized."""
    cdf = np.cumsum(probs.astype(np.float64), axis=1)
    cdf /= cdf[:, -1:]
    cdf[:, -1] = 1.0
    flat = (cdf + np.arange(probs.shape[0])[:, None]).ravel()
    u = rng.random(rows.shape[0]) + rows
    out = np.searchsorted(flat, u, side="left") - rows * probs.shape[1]
    return np.clip(out, 0, probs.shape[1] - 1).astype(np.int32)


class Fleet:
    """Per-client structure of one fleet: sizes, P(y) states, styles."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        n, c = cfg["num_clients"], cfg["num_classes"]
        rng = stream(seed, 1)
        order = rng.permutation(n)
        self.sizes = quantile_sizes(cfg)[order]
        # each client's size level (its sorted position's), for flips
        self.level = order // (n // cfg.get("size_levels", n))
        self.style_of = rng.integers(0, cfg["num_styles"], n)
        alpha = [cfg["alpha"]] * c
        p0 = rng.dirichlet(alpha, n)
        p1 = rng.dirichlet(alpha, n)
        while True:       # keep the two states of each client far apart
            near = np.flatnonzero(sym_kl_rows(p0, p1)
                                  <= KL_MARGIN * cfg["server"]["refresh_kl"])
            if near.size == 0:
                break
            p1[near] = rng.dirichlet(alpha, near.size)
        self.py = np.stack([p0, p1]).astype(np.float32)      # [2, N, C]
        self.py /= self.py.sum(-1, keepdims=True)
        self.key_seed = int(rng.integers(0, 2 ** 31))

    def label_dists(self, state: np.ndarray) -> np.ndarray:
        """[N] states -> [N, C] P(y), the rows of the two state tables."""
        return np.where(np.asarray(state, bool)[:, None], self.py[1],
                        self.py[0])


def flip_sets(fleet: Fleet, n_flip: int, rounds: int, rng) -> list:
    """The clients that switch state in each round after round 0: with size
    levels, the same count at the same levels every round; else a uniform
    draw."""
    n = fleet.sizes.shape[0]
    levels = fleet.cfg.get("size_levels")
    if levels is None:
        return [np.sort(rng.choice(n, n_flip, replace=False))
                for _ in range(1, rounds)]
    members = [np.flatnonzero(fleet.level == lv) for lv in range(levels)]
    if n_flip >= levels:
        if n_flip % levels:
            raise ValueError(f"{n_flip} flips do not spread over {levels} "
                             "levels")
        take = {lv: n_flip // levels for lv in range(levels)}
    else:
        take = {int((i + 0.5) * levels / n_flip): 1 for i in range(n_flip)}
    return [np.sort(np.concatenate([rng.choice(members[lv], t, replace=False)
                                    for lv, t in take.items()]))
            for _ in range(1, rounds)]


class Traffic:
    """The rounds of one cell, all made in set-up.  The surface the harness
    reads: ``rounds``, ``warm_rounds``, ``speeds``, ``available[r]``,
    ``drift(r)`` (the scenario's per-client drift value), ``label_dists(r)``
    (the P(y) each client reports in round r), ``data`` (the dataset
    surface the program reads), and for a fleet that uploads,
    ``upload_rows(r)`` (the summary each client uploads in round r)."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg = cfg
        n = cfg["num_clients"]
        self.fleet = Fleet(cfg, seed)
        rng = stream(seed, 0)
        r_total = int(mix["plan_rounds"])
        n_flip = int(round(mix["drift_share"] * n))
        self.flips = [np.arange(n, dtype=np.int64)]   # round 0: the fleet
        self.flips += flip_sets(self.fleet, n_flip, r_total, rng)
        self.states = np.zeros((r_total, n), np.uint8)
        for r in range(1, r_total):
            self.states[r] = self.states[r - 1]
            self.states[r, self.flips[r]] ^= 1
        n_avail = int(round(mix["available_share"] * n))
        self.available = np.zeros((r_total, n), bool)
        for r in range(r_total):
            self.available[r, rng.choice(n, n_avail, replace=False)] = True
        self.speeds = rng.lognormal(0.0, 0.5, n)
        self.warm_rounds = int(mix["warm_rounds"])
        self.computed = cfg["summaries"] == "computed"
        self.data = ImageData(self.fleet, make_images=self.computed)
        self.uploads = None if self.computed else Uploads(self.fleet)

    @property
    def rounds(self) -> int:
        return self.states.shape[0]

    def drift(self, r: int) -> np.ndarray:
        return self.states[r]

    def label_dists(self, r: int) -> np.ndarray:
        return self.fleet.label_dists(self.states[r])

    def client_data(self, r: int, cid: int):
        """(images, labels) client ``cid`` holds in round ``r``."""
        images, labels, _ = self.data.client_data(cid, self.states[r][cid])
        return images, labels

    def upload_rows(self, r: int) -> np.ndarray:
        s = self.states[r].astype(bool)
        rows = self.uploads.rows
        return np.where(s[:, None], rows[1], rows[0])

    def uploads_of(self, r: int, ids) -> dict:
        rows, state = self.uploads.rows, self.states[r]
        return {c: rows[state[c]][c] for c in ids}


def make(cfg: dict, mix: dict, seed: int) -> Traffic:
    return Traffic(cfg, mix, seed)


class Spec:
    """The ``DatasetSpec`` fields the program reads."""

    def __init__(self, cfg: dict):
        self.name = cfg["name"]
        self.num_clients = cfg["num_clients"]
        self.num_classes = cfg["num_classes"]
        self.feature_shape = tuple(cfg["feature_shape"])


class ImageData:
    """The ``FederatedDataset`` surface ``RoundContext`` reads, served from
    arrays made in set-up: ``client_data(c, drift)`` slices the images of
    client ``c`` in state ``int(drift)``."""

    def __init__(self, fleet: Fleet, make_images: bool):
        cfg = fleet.cfg
        self.spec, self.fleet, self.sizes = Spec(cfg), fleet, fleet.sizes
        c, p_dim = cfg["num_classes"], cfg["proto_dim"]
        d_pix = int(np.prod(cfg["feature_shape"]))
        key = jax.random.PRNGKey(fleet.key_seed)
        k_proj, k_cls, k_sty, k_data, k_test = jax.random.split(key, 5)
        self._proj = jax.random.normal(k_proj, (p_dim, d_pix)) / math.sqrt(p_dim)
        self._cls = jax.random.normal(k_cls, (c, p_dim)) * cfg["class_scale"]
        self._sty = jax.random.normal(k_sty, (cfg["num_styles"], p_dim)) \
            * cfg["style_scale"]
        self._k_test = k_test
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])
        self.labels, self.images = [], []
        if not make_images:
            return
        owner = np.repeat(np.arange(cfg["num_clients"]), self.sizes)
        total = owner.shape[0]
        rng = stream(fleet.key_seed, 2)
        for s in range(2):
            lab = draw_categorical(rng, fleet.py[s], owner)
            imgs = np.empty((total, d_pix), np.float32)
            for lo in range(0, total, IMAGE_CHUNK):
                hi = min(lo + IMAGE_CHUNK, total)
                pad = np.zeros(IMAGE_CHUNK, np.int32)    # one shape
                pl, ps = pad.copy(), pad.copy()
                pl[:hi - lo] = lab[lo:hi]
                ps[:hi - lo] = fleet.style_of[owner[lo:hi]]
                out = _images(self._proj, self._cls, self._sty,
                              jnp.asarray(pl), jnp.asarray(ps),
                              jax.random.fold_in(jax.random.fold_in(k_data, s),
                                                 lo),
                              cfg["noise_scale"])
                imgs[lo:hi] = np.asarray(out)[:hi - lo]
            self.labels.append(lab)
            self.images.append(imgs.reshape(-1, *cfg["feature_shape"]))

    def client_label_dists(self, drift) -> np.ndarray:
        return self.fleet.label_dists(drift)

    def client_data(self, cid: int, drift: float = 0.0):
        s = int(drift)
        lo, hi = self.offsets[cid], self.offsets[cid + 1]
        return (self.images[s][lo:hi], self.labels[s][lo:hi],
                np.ones(hi - lo, bool))

    def test_set(self, per_class: int = 8):
        """The payload's test set at the program's shape, made on the
        device (the benchmark's window never evaluates)."""
        cfg = self.fleet.cfg
        labels = jnp.repeat(jnp.arange(cfg["num_classes"], dtype=jnp.int32),
                            per_class)
        styles = labels % cfg["num_styles"]
        imgs = _images(self._proj, self._cls, self._sty, labels, styles,
                       self._k_test, cfg["noise_scale"],
                       shape=tuple(cfg["feature_shape"]))
        return imgs, labels


@jax.jit
def _latent_images(proj, cls, sty, labels, styles, key, noise_scale):
    lat = cls[labels] + sty[styles] + noise_scale * jax.random.normal(
        key, (labels.shape[0], cls.shape[1]))
    return jax.nn.sigmoid(jnp.dot(lat, proj,
                                  precision=jax.lax.Precision.HIGHEST))


def _images(proj, cls, sty, labels, styles, key, noise_scale, shape=None):
    """Images of the given labels and style groups: sigmoid of the class
    prototype plus the style plus noise, projected to pixels."""
    img = _latent_images(proj, cls, sty, labels, styles, key, noise_scale)
    return img if shape is None else img.reshape(-1, *shape)


class Uploads:
    """Every client's upload in both states: [2, N, C*H + C] float32 on the
    host, per-label coreset means then P(y)."""

    def __init__(self, fleet: Fleet):
        cfg = fleet.cfg
        n, c = cfg["num_clients"], cfg["num_classes"]
        h, k = cfg["server"]["encoder_dim"], cfg["server"]["coreset_k"]
        key = jax.random.PRNGKey(fleet.key_seed)
        k_cls, k_sty, k_noise = jax.random.split(jax.random.fold_in(key, 17), 3)
        cls = jax.random.normal(k_cls, (c, h))
        sty = jax.random.normal(k_sty, (cfg["num_styles"], h)) * 0.5
        rng = stream(fleet.key_seed, 3)
        kept = np.minimum(fleet.sizes, k)       # coreset rows per client
        owner = np.repeat(np.arange(n), kept)
        self.rows = []
        for s in range(2):
            lab = draw_categorical(rng, fleet.py[s], owner)
            counts = np.zeros((n, c), np.float32)
            np.add.at(counts, (owner, lab), 1.0)
            rows = _upload_rows(cls, sty, jnp.asarray(counts),
                                jnp.asarray(fleet.style_of, np.int32),
                                jnp.asarray(fleet.py[s]),
                                jax.random.fold_in(k_noise, s))
            self.rows.append(np.asarray(rows))


@jax.jit
def _upload_rows(cls, sty, counts, style_of, py, key):
    n, c = counts.shape
    noise = jax.random.normal(key, (n, c, cls.shape[1])) * 0.3
    means = (cls[None] + sty[style_of][:, None, :]
             + noise / jnp.sqrt(jnp.maximum(counts, 1.0))[..., None])
    means = jnp.where(counts[..., None] > 0, means, 0.0)
    return jnp.concatenate([means.reshape(n, -1), py], axis=-1)
