"""The least a chip must move over the interconnect for the trees that
were grown by the data-parallel learner, and the chips' peak for it.

The algorithm is the reference's (LightGBM data_parallel_tree_learner.cpp):
every machine builds local histograms of the root and of each split's
smaller child over all features, and the histograms are summed across
machines; the larger child is parent minus smaller and moves nothing.
A histogram is ``features x max_bin x (gradient, hessian)`` sums, taken
here at 4 bytes a sum (the int32 the configuration's quantized path
accumulates).  A sum over ``n`` chips that leaves the result on every
chip moves at least ``(n - 1) / n`` of the histogram out of each chip
(reduce-scatter) and as much in (all-gather); links are full duplex, so
the time is bounded by one direction: ``bytes x (n - 1) / n`` a chip.
Nothing here knows how the program packs, splits or schedules its
exchange.
"""
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks_ici.json")


def ici_peak(device_kind):
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "peaks_ici.json: add it with its source, there is "
                       "no default")
    return table[device_kind]["ici_bytes_per_s"]


def exchanged_histograms(trees):
    """Histograms the learner must sum across chips: per tree the root's
    and one for every split (its smaller child's)."""
    return sum(1 + len(t["left_child"]) for t in trees)


def exchange_bytes_per_chip(features, max_bin, trees, chips, sum_bytes=4):
    """Bytes each chip must send (and receive) for ``trees``."""
    hist = features * max_bin * 2 * sum_bytes
    return exchanged_histograms(trees) * hist * (chips - 1) / chips


def least_exchange_seconds(device_kind, features, max_bin, trees, chips):
    return exchange_bytes_per_chip(features, max_bin, trees,
                                   chips) / ici_peak(device_kind)
