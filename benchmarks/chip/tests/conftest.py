"""Tiny cells for CPU tests of the harness: the program's smoke presets
(float32, two layers) under the real cells' traffic, scaled down."""

from __future__ import annotations

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import spec  # noqa: E402

if spec.SRC not in sys.path:
    sys.path.insert(1, spec.SRC)

TINY_MAMBA = {
    "name": "mamba2-smoke", "arch": "mamba2-370m", "preset": "smoke",
    "config": {"d_model": 64, "n_layer": 2, "vocab_size": 256, "d_state": 16,
               "d_conv": 4, "expand": 2, "headdim": 16, "chunk_size": 16,
               "tie_embeddings": True, "torch_dtype": "float32"}}
TINY_MINICPM = {
    "name": "minicpm-smoke", "arch": "minicpm-2b", "preset": "smoke",
    "config": {"hidden_size": 48, "num_hidden_layers": 2,
               "num_attention_heads": 6, "num_key_value_heads": 6,
               "head_dim": 8, "intermediate_size": 96, "vocab_size": 211,
               "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
               "tie_word_embeddings": True, "torch_dtype": "float32"}}


def tiny_cell(name: str) -> spec.Cell:
    """A real cell with the smoke configuration and small traffic."""
    real = spec.load_cell(name)
    traffic = copy.deepcopy(real.traffic)
    if traffic["kind"] == "train":
        config = TINY_MAMBA
        traffic.update(seq_len=64, global_batch=4,
                       ckpt_every=min(traffic["ckpt_every"], 4))
        traffic["corpus"].update(rows=96, doc_len_median=48)
    else:
        config = TINY_MINICPM
        traffic.update(slots=4, max_len=64, rate_per_s=10.0,
                       prompt_len={"8": 0.5, "16": 0.5}, drain_s=30,
                       check_requests=3)
        traffic["output_len"].update(median=6, sigma=0.5, min=2, max=16)
    return spec.Cell(name=real.name, chips=1, config=copy.deepcopy(config),
                     traffic=traffic, end_to_end=real.end_to_end,
                     per_layer=real.per_layer)


def shallow_cell(name: str, layers: int = 8) -> spec.Cell:
    """A real cell at its configuration's widths and vocabulary with
    ``layers`` layers, under the tiny cell's traffic: logits at the
    published scale, small enough for the CPU."""
    cell = tiny_cell(name)
    real = spec.load_cell(name)
    config = copy.deepcopy(real.config)
    key = "n_layer" if "n_layer" in config["config"] else "num_hidden_layers"
    config["config"][key] = layers
    cell.config = config
    return cell


@pytest.fixture
def tiny():
    return tiny_cell


@pytest.fixture
def shallow():
    return shallow_cell
