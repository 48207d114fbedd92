"""Serving: the mean number of requests per wave the server formed in the
window (``ServeStats.wave_sizes``): how much coalescing does."""


def read(run: dict):
    sizes = run["waves"]["sizes"]
    return sum(sizes) / len(sizes) if sizes else None
