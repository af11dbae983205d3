"""A tiny transformer family for CPU tests of the harness, written into
a test root as ``bench/families/tinylm.py``: the clients are variants
of a reduced repo configuration (``base``, cut to ``base_units`` units
at ``d_model``, its vocabulary sliced to ``vocab_size``) that differ in
depth (``n_units``) and ``d_ff`` (``ffn_scale``), as the system's own
transformer cohort tests build them.

A configuration lists ``archs`` (name -> ``{"n_units", "ffn_scale"}``)
and ``clients`` (pairs of arch name and count, in client order).
"""
from __future__ import annotations

from typing import List

SHARED = ("base", "base_units", "d_model", "vocab_size")


def client_dicts(config: dict) -> List[dict]:
    """Each client's architecture as a plain dict, in client order."""
    shared = {k: config[k] for k in SHARED}
    out = []
    for arch, count in config["clients"]:
        out += [dict(shared, name=arch, **config["archs"][arch])
                for _ in range(int(count))]
    return out


def system_config(c: dict):
    """One client's ``ModelConfig``."""
    from repro.configs import get_config, reduced
    from repro.core import tfamily
    base = reduced(get_config(c["base"]), n_units=c["base_units"],
                   d_model=c["d_model"], seed_vocab=c["vocab_size"])
    return tfamily.make_variant(base, n_units=c["n_units"],
                                ffn_scale=c["ffn_scale"])


def program_cohort(config: dict):
    """``(family, client_cfgs)`` in the system's own types."""
    from repro.core import TransformerFamily
    return TransformerFamily(), [system_config(c)
                                 for c in client_dicts(config)]


def train_flops_per_sample(c: dict, mix: dict) -> int:
    """Forward + backward FLOPs of one sequence of the mix's ``seq_len``
    tokens: 6 per MAC of the attention projections, the SwiGLU FFN and
    the output head (attention scores, norms and the embedding gather
    left out)."""
    m = system_config(c)
    attn = m.d_model * m.head_dim * 2 * (m.n_heads + m.n_kv_heads)
    ffn = 3 * m.d_model * m.d_ff
    macs = m.n_layers * (attn + ffn) + m.d_model * m.vocab_size
    return 6 * macs * int(mix["seq_len"])
