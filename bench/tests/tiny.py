"""A benchmark cell cut to a size the CPU runs in seconds.

The cell's own limits, traffic recipe and mesh; the model's widths, depth
and vocabulary, the sequence and the sync interval shrunk.
"""

import dataclasses
import json

import run as R

TINY_MODEL = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                  d_ff=256, vocab_size=512, max_position_embeddings=128)


def prepared_cell(name: str, config: str, traffic: str, chips: int) -> R.Cell:
    """A cell whose files are in ``bench/`` but not in BENCHMARK.json."""
    def read(*parts):
        return json.loads(R.BENCH.joinpath(*parts).read_text())
    return R.Cell(name=name, chips=chips,
                  config=read("configs", f"{config}.json"),
                  traffic=read("traffic", f"{traffic}.json"),
                  check=read("workloads", f"{name}.json"))


# Cells whose files are in bench/ but which BENCHMARK.json does not list yet
# (PERF.md, Open questions): (name, config, traffic, chips).
PREPARED = [("gpt2s-4chip-g4-h10", "gpt2-small", "seq1024.b8.g4.h10", 4)]
NAMES = [w["name"] for w in json.loads(
    (R.ROOT / "BENCHMARK.json").read_text())["workloads"]] + \
    [p[0] for p in PREPARED]


def cell_named(name: str) -> R.Cell:
    for prepared in PREPARED:
        if prepared[0] == name:
            return prepared_cell(*prepared)
    return R.load_cell(name)


def tiny_cell(cell) -> R.Cell:
    """``cell`` (a Cell, or the name of one in ``NAMES``) cut down."""
    if isinstance(cell, str):
        cell = cell_named(cell)
    traffic = dict(cell.traffic, seq_len=64, chain=dict(
        cell.traffic.get("chain", {}), pool_steps=16))
    train = dict(traffic["train"], sync_interval=4)
    traffic["train"] = train
    check = dict(cell.check)
    if check["check_steps"] >= cell.traffic["train"]["sync_interval"]:
        check["check_steps"] = train["sync_interval"]  # through the sync
    return dataclasses.replace(cell, config=dict(cell.config, **TINY_MODEL),
                               traffic=traffic, check=check)
