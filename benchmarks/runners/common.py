"""What the runners share about the program's models."""
from __future__ import annotations


def named_arrays(net) -> dict:
    """{parameter name: device array}, the form the plain reference takes."""
    return {n: p._value for n, p in net.named_parameters()}


def redraw_embeddings(net, seed: int, std: float) -> None:
    """Every `nn.Embedding` table ~ N(0, std), from the seed, on the device,
    through the public `set_value`. `nn.Embedding` initialises N(0, 1); with
    a weight-tied head that gives logits of std ~30 and a first loss near
    117 (PERF.md, PR 22), against which no tolerance means anything."""
    import jax
    key = jax.random.key(seed % (2 ** 32))
    for name, p in net.named_parameters():
        if name.endswith("_embeddings.weight"):
            key, sub = jax.random.split(key)
            p.set_value(jax.random.normal(sub, tuple(p.shape)) * std)
