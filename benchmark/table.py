"""The deployment's table: generated from ``--seed`` by the generator the
configuration names (``generators/<name>.py``), built into segments and
registered with the controller; and the same rows again, block by block,
for the reference.

Segments are independent and the builder is host NumPy that never imports
JAX, so they are built in worker processes (spawned, so none inherits the
chip) while the parent brings JAX up. The reference's rows are generated
again from the seed after the window, a segment at a time, so set-up
neither waits for them nor keeps them in memory.
"""

from __future__ import annotations

import importlib.util
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def generator_of(config: dict):
    path = HERE / "generators" / f"{config['generator']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no generator {path}")
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def table_schema(config: dict):
    from pinot_tpu.spi.data_types import Schema
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    cols = config["columns"]
    schema = Schema.build(
        config["table"],
        dimensions=[(c, v["type"]) for c, v in cols.items()
                    if v["role"] == "dimension"],
        metrics=[(c, v["type"]) for c, v in cols.items()
                 if v["role"] == "metric"])
    table_config = TableConfig(
        table_name=config["table"], indexing=IndexingConfig(
            no_dictionary_columns=[c for c, v in cols.items()
                                   if v["encoding"] == "raw"]))
    return schema, table_config


def build_segment(config: dict, rows_per_segment: int, seed: int, seg: int,
                  data_dir: str) -> str:
    """Runs in a worker process: generate one segment and build it."""
    from pinot_tpu.segment.builder import SegmentBuilder

    generator = generator_of(config)
    schema, table_config = table_schema(config)
    cols = generator.segment_columns(config, rows_per_segment, seed, seg)
    for c, names in generator.dictionaries(config).items():
        if c in cols:  # a string column, generated as codes
            cols[c] = np.asarray(names, dtype=object)[cols[c]]
    name = f"{config['table']}_{seg}"
    path = str(Path(data_dir) / config["table"] / name)
    SegmentBuilder(schema, table_config, name).build(cols, path)
    return path


class BuildPool:
    """Builds the table's segments in worker processes from the moment it
    is made; ``register`` waits for them and registers the table."""

    def __init__(self, config: dict, rows_per_segment: int, seed: int,
                 data_dir):
        self.config, self.rows = config, rows_per_segment
        # one round where the cores nearly reach: the work waits on fresh
        # memory as much as it computes
        workers = min(config["segments"],
                      max(1, (os.cpu_count() or 2) * 3 // 2))
        self._pool = ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"))
        self._futures = [
            self._pool.submit(build_segment, config, rows_per_segment, seed,
                              seg, str(data_dir))
            for seg in range(config["segments"])]

    def register(self, controller) -> None:
        schema, table_config = table_schema(self.config)
        paths = [f.result() for f in self._futures]
        self.close()
        controller.add_schema(schema.to_json())
        table = controller.create_table(table_config.to_json())
        for seg, path in enumerate(paths):
            controller.add_segment(
                table, f"{self.config['table']}_{seg}",
                {"location": path, "numDocs": self.rows})

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)
