"""Vertices drawn uniformly, with replacement, from the set the
deployment offers for the role (sources or targets)."""


def draw(rng, vertices, size=None):
    return rng.choice(vertices, size=size)
