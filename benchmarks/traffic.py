"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``benchmarks/traffic/``; a driver hands it, with the seed, to the
function here that makes its kind of input. The same seed gives the same inputs.
Every seed gets the same set of sizes in another order, so that the seed
changes the inputs and not the amount of work."""

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        return json.load(fh)


def image_frames(params, seed, image, channels):
    """A column of ``rows_per_pass`` uint8 HWC images over ``distinct_images``
    images drawn from the seed: row ``i`` holds image ``i % distinct_images``,
    as one (N, H, W, C) array, the form that is staged on the device in one
    transfer. Returns (images, column)."""
    n, k = params["rows_per_pass"], params["distinct_images"]
    images = np.random.default_rng(seed).integers(
        0, 256, (k, image, image, channels), dtype=np.uint8)
    return images, images[np.arange(n) % k]


def lognormal_grid(median, sigma, lo, hi, n):
    """``n`` lengths at the evenly spaced quantiles of a log-normal clipped
    to [lo, hi]: the distribution's shape with no sampling noise."""
    from statistics import NormalDist
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def closed_loop_requests(params, seed, vocab):
    """Per client, the cycle of ``(prompt tokens, output length)`` it repeats.

    The mix is a fixed set of ``clients x requests_per_client`` pairs: prompt
    lengths and output lengths are the evenly spaced quantiles of two clipped
    log-normals, paired by a permutation that belongs to the mix and not to
    the seed, ``prompt + output`` held to ``max_total`` by shortening the
    output. The pairs are dealt to the clients in a snake over their output
    lengths, so every client's cycle holds about the same work and lasts about
    as long. The seed decides which client gets which bundle, the order within
    each cycle and the tokens: every seed carries the same work in another
    order."""
    rng = np.random.default_rng(seed)
    c, k = params["clients"], params["requests_per_client"]
    n = c * k
    p, o = params["prompt"], params["output"]
    plens = lognormal_grid(p["median"], p["sigma"], p["min"], p["max"], n)
    olens = lognormal_grid(o["median"], o["sigma"], o["min"], o["max"], n)
    olens = olens[np.random.default_rng(params["pairing"]).permutation(n)]
    olens = np.minimum(olens, params["max_total"] - plens)
    order = np.lexsort((plens, olens))          # by output, then by prompt
    bundles = [[] for _ in range(c)]
    for rank, i in enumerate(order):
        lap, pos = divmod(rank, c)
        bundles[pos if lap % 2 == 0 else c - 1 - pos].append(i)
    plans = []
    for b in rng.permutation(c):
        cycle = rng.permutation(bundles[b])
        plans.append([(rng.integers(1, vocab, int(plens[i])).astype(np.int32),
                       int(olens[i])) for i in cycle])
    return plans
