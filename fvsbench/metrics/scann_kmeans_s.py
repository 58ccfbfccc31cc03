"""Seconds of set-up in the ScaNN build's k-means (the program's
`scann.kmeans` spans: the leaves' and, with two levels, the branches'),
on the host's clock; None for a build without them."""


def read(run, trace):
    return run.shape.get("scann_kmeans_s")
