"""Sizes at which the benchmark's cells are rehearsed on the CPU: the
configurations' widths, a field of a few dozen hyper-blocks, stripes of 4
and slices of a few stripes."""
from __future__ import annotations

E3SM_HB_BYTES = 5 * 1536 * 4
XGC_HB_BYTES = 8 * 1521 * 4

OVERRIDES = {
    "e3sm-compress": {
        "config": {"shape": [60, 48, 96]},
        "traffic": {"chunk_hyperblocks": 4, "slice_bytes": 12 * E3SM_HB_BYTES,
                    "check_chunks": 4}},
    "e3sm-decompress": {
        "config": {"shape": [60, 48, 96]},
        "traffic": {"chunk_hyperblocks": 4, "slice_bytes": 12 * E3SM_HB_BYTES,
                    "check_chunks": 4}},
    "xgc-compress": {
        "config": {"shape": [8, 128, 39, 39]},
        "traffic": {"chunk_hyperblocks": 4, "slice_bytes": 16 * XGC_HB_BYTES,
                    "check_chunks": 4}},
}


def run(name: str, **kwargs) -> dict:
    """One run of ``name`` at the quick size; JAX's persistent compile cache
    is left as the test process has it."""
    from unittest import mock

    from bench import run as bench_run
    from repro.core import exec as exec_mod
    with mock.patch.object(exec_mod, "use_compile_cache", lambda: None):
        return bench_run.run_cell(name, kwargs.pop("seed", 2**31 + 7),
                                  kwargs.pop("seconds", 0.5),
                                  kwargs.pop("trace", False),
                                  require_tpu=False,
                                  overrides=OVERRIDES[name], **kwargs)
