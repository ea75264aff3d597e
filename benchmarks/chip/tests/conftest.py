"""Tiny cells for CPU tests of the harness: each configuration's smoke
form (``tiny/<config>.json``: float32, two layers) under the real cells'
traffic, scaled down."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import spec  # noqa: E402

if spec.SRC not in sys.path:
    sys.path.insert(1, spec.SRC)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def tiny_cell(name: str, bench: dict = None, tiny_dir: str = TINY
              ) -> spec.Cell:
    """A real cell with small traffic and its configuration's form for the
    CPU, ``<tiny_dir>/<config>.json`` laid over the configuration file:
    the sizes (the smoke preset, float32, two layers) are the tiny
    form's, and ``reference``, ``fields`` and ``work`` the file's."""
    bench = bench or spec.benchmark()
    real = spec.load_cell(name, bench)
    config = {w["name"]: w["config"] for w in bench["workloads"]}[name]
    with open(os.path.join(tiny_dir, config + ".json")) as f:
        tiny = json.load(f)
    traffic = copy.deepcopy(real.traffic)
    if traffic["kind"] == "train":
        traffic.update(seq_len=64, global_batch=4,
                       ckpt_every=min(traffic["ckpt_every"], 4))
        traffic["corpus"].update(rows=96, doc_len_median=48)
    else:
        traffic.update(slots=4, max_len=64, rate_per_s=10.0,
                       prompt_len={"8": 0.5, "16": 0.5}, drain_s=30,
                       check_requests=3)
        traffic["output_len"].update(median=6, sigma=0.5, min=2, max=16)
    return spec.Cell(name=real.name, chips=1,
                     config={**copy.deepcopy(real.config), **tiny},
                     traffic=traffic, end_to_end=real.end_to_end,
                     per_layer=real.per_layer, config_file=real.config_file)


def shallow_cell(name: str, layers: int = 8) -> spec.Cell:
    """A real cell at its configuration's widths and vocabulary with
    ``layers`` layers, under the tiny cell's traffic: logits at the
    published scale, small enough for the CPU."""
    cell = tiny_cell(name)
    real = spec.load_cell(name)
    config = copy.deepcopy(real.config)
    fields = {**spec.FIELDS, **config.get("fields", {})}
    key, = [k for k in config["config"] if fields.get(k) == "n_layers"]
    config["config"][key] = layers
    cell.config = config
    return cell


@pytest.fixture
def tiny():
    return tiny_cell


@pytest.fixture
def shallow():
    return shallow_cell
