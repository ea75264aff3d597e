"""Operations and bytes that the work needs, from a configuration's shapes.

Model FLOPs count each multiply-add as two operations and leave out what a
program recomputes (rematerialization) or computes and throws away (masked
halves, padded rows, free serving slots).  A training step is the forward
pass and twice it for the backward.

These counts hold for the layers this file knows.  A configuration whose
work they miscount (experts, a layer pattern) names a module of its own
under ``"work"`` in its file, and the per-layer readers count through
``of(rec)``: that module is this file with the module's definitions laid
over it, so a name it leaves out is this file's, and a helper it
overrides is the one this file's functions call too.
"""

from __future__ import annotations

import os
import sys
import types

_WORK: dict = {}


def of(rec):
    """The counts for a run's record: its configuration's ``work`` module
    (a path relative to this directory, or absolute), else this module."""
    path = rec.cell.config.get("work")
    if path is None:
        return sys.modules[__name__]
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.normpath(os.path.join(here, path))
    if path not in _WORK:
        mod = types.ModuleType("work_" + os.path.basename(path)[:-3])
        mod.__file__ = path
        for src in (os.path.abspath(__file__), path):
            with open(src) as f:
                exec(compile(f.read(), src, "exec"), mod.__dict__)
        _WORK[path] = mod
    return _WORK[path]


def _attn_layer_matmul_params(cfg) -> int:
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return D * H * dh + 2 * D * KV * dh + H * dh * D


def _ssm_layer_matmul_params(cfg) -> int:
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    return cfg.d_model * (2 * di + 2 * ns + nh) + di * cfg.d_model


def _mlp_layer_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_ff if cfg.d_ff else 0


def matmul_params(cfg) -> int:
    """Weights that take part in a matrix product per token, the tied
    output head included and the embedding gather excluded."""
    per_layer = _mlp_layer_params(cfg)
    if cfg.has_attention:
        per_layer += _attn_layer_matmul_params(cfg)
    if cfg.has_ssm:
        per_layer += _ssm_layer_matmul_params(cfg)
    return cfg.n_layers * per_layer + cfg.d_model * cfg.vocab_size


def ssd_flops_per_token(cfg, seq_len: int) -> float:
    """The SSD mixer's own terms per token and layer, chunked form: the
    causal half of C·Bᵀ and of its product with x inside a chunk, the
    chunk's state and the read of the carried state."""
    ns, nh, hd = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    q = min(cfg.ssm_chunk, seq_len)
    keys = (q + 1) / 2  # mean causal keys per position inside a chunk
    return 2 * keys * ns + 2 * keys * nh * hd + 2 * (2 * nh * hd * ns)


def attn_flops_per_token(cfg, context: float) -> float:
    """Scores and weighted values of one query over ``context`` keys, one
    layer."""
    return 2 * 2 * context * cfg.n_heads * cfg.d_head


def forward_flops(cfg, seq_len: int) -> float:
    """Forward FLOPs for one sequence of ``seq_len`` tokens."""
    per_token = 2.0 * matmul_params(cfg)
    if cfg.has_ssm:
        per_token += cfg.n_layers * ssd_flops_per_token(cfg, seq_len)
    total = per_token * seq_len
    if cfg.has_attention:  # causal: query i sees i + 1 keys
        total += cfg.n_layers * attn_flops_per_token(cfg, (seq_len + 1) / 2) \
            * seq_len
    return total


def train_step_flops(cfg, batch: int, seq_len: int) -> float:
    return 3.0 * batch * forward_flops(cfg, seq_len)


def weight_bytes(cfg) -> int:
    """Bytes of the parameters as served (every weight read once)."""
    import numpy as np

    return cfg.param_count() * np.dtype(cfg.param_dtype).itemsize


def kv_bytes_per_position(cfg) -> int:
    import numpy as np

    if not cfg.has_attention:
        return 0
    return (cfg.n_layers * 2 * cfg.n_kv_heads * cfg.d_head
            * np.dtype(cfg.compute_dtype).itemsize)


def decode_interval(cfg, live_positions) -> dict:
    """One decode interval over the occupied slots, each at its own number
    of cached positions: FLOPs, the bytes it needs (the weights once, the
    K/V of live positions), and the least time at the given peaks."""
    live = [int(p) for p in live_positions]
    flops = sum(2.0 * matmul_params(cfg)
                + cfg.n_layers * attn_flops_per_token(cfg, p + 1)
                for p in live)
    needed = weight_bytes(cfg) + kv_bytes_per_position(cfg) * sum(
        p + 1 for p in live)
    return {"flops": flops, "bytes": float(needed)}


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound sets it)."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
