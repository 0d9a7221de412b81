"""Tiny copies of the benchmark's configurations, for CPU tests: the same
objective, solver, traffic and limits at a few clients and features."""

import contextlib
import copy
import time

import jax

import harness
import peaks as peaks_lib
import registry


def shrink(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg["geometry"].update(n_clients=8, samples_per_client=64, dim=24)
    if cfg["fstar"]["method"] == "newton_cg":
        cfg["fstar"].update(steps=8, cg_iters=40)
    return cfg


def config(cell_name: str) -> dict:
    return shrink(registry.config(registry.cell(cell_name)["config"]))


@contextlib.contextmanager
def harness_on_cpu():
    """The harness as it runs, with every configuration at tiny size, the
    chips it asks for taken from whatever JAX finds, and no peaks table."""
    saved = registry.config, harness.devices_for, peaks_lib.peaks
    full_config = registry.config
    registry.config = lambda name, root=registry.ROOT: shrink(full_config(name, root))
    harness.devices_for = lambda chips: jax.devices()[:chips]
    peaks_lib.peaks = lambda kind: None
    try:
        yield
    finally:
        registry.config, harness.devices_for, peaks_lib.peaks = saved


def run(cell_name: str, seed: int = 2**33 + 5):
    """One run of the cell at tiny size on the CPU, as the command line
    makes it."""
    with harness_on_cpu():
        return harness.run_cell(cell_name, seed, 0.2, False, t_process=time.perf_counter())
