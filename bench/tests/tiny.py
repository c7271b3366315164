"""Shrunk configurations for the benchmark's CPU tests."""
import pathlib
import time

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 17


def tiny(cfg: dict) -> dict:
    """``cfg`` at a size the CPU runs in seconds: 4k stars, m = 128 (the
    quadratic fit needs 45), 128 hosts, 6 iterations, and a target loose
    enough that searches reach it within a second or two."""
    cfg["stripe"].update(n_stars=4000, n_quad=512)
    cfg["anm"].update(m_regression=128, m_line_search=128, max_iterations=6)
    cfg["fleet"].update(n_hosts=128)
    cfg["target"]["tolerance"] = 0.05
    return cfg


def cell_config(cell: str, root: pathlib.Path = ROOT) -> dict:
    spec = harness.load_benchmark(root)
    return tiny(harness.config_of(spec, root,
                                  harness.workload(spec, cell)["config"]))


def run_tiny(cell: str, seconds: float = 1.5, root: pathlib.Path = ROOT,
             **kw) -> dict:
    """One run of ``cell`` at the tiny size, skipping only the look for a
    chip."""
    return harness.run_cell(root, cell, SEED, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            config=cell_config(cell, root),
                            log=lambda msg: None, **kw)
