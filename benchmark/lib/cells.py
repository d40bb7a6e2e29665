"""Where a cell's files live and how they become the system under test.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own, found by name:

    configs/<config>.json      sizes, source, reduced, assumed
    traffic/<traffic>.json     parameters of one traffic mix
    workloads/<cell>.json      config + traffic + chips + driver + settings
    layer_metrics/<name>.json  one per-layer metric and its reader
"""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load("configs", name)


def load_traffic(name: str) -> dict:
    return _load("traffic", name)


def load_workload(name: str) -> dict:
    return _load("workloads", name)


def load_layer_metrics(workload_name: str, workload: dict) -> list:
    """The per-layer metrics of a cell, in file-name order: those whose
    file lists the cell under ``cells`` and those the cell's own file
    names under ``layer_metrics`` -- so a later PR can attach a new
    metric to an old cell, or an old metric to a new cell, by adding a
    file and editing none."""
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    named = set(workload.get("layer_metrics", ()))
    out = []
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".json"):
            metric = _load("layer_metrics", fname[:-5])
            if workload_name in metric["cells"] or metric["name"] in named:
                out.append(metric)
    return out


def build_model(config: dict, settings: dict):
    """The repo's GPT at the configuration's sizes.  ``settings`` are the
    cell's step-shape choices (flash block, loss chunk, remat) and its
    learning rate."""
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)

    kw = dict(config["model"])
    block = settings.get("flash_block")
    return GPT(TransformerConfig(
        **kw, fused_loss=True, remat=bool(settings.get("remat", False)),
        loss_chunk_rows=settings.get("loss_chunk_rows", 1024),
        flash_block_q=block, flash_block_k=block),
        lr=float(settings.get("lr", 3e-4)))
