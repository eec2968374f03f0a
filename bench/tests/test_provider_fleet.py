"""The provider fleet's own pieces of the harness, on the CPU: the shared
scan's roofline count on known shapes, and the shape the cell hands it.
Run by hand with the harness's other tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def test_shared_scan_count_on_known_shapes():
    m = run.load_module(BENCH / "metrics" / "shared_scan_roofline.py")
    # 10 steps x (8*100*4*5 + 4*100) = 10 x 16,400 bytes at 1e9 B/s
    assert m.least_seconds((100, 4, 5, 10), {"hbm_bytes_per_s": 1e9}) == \
        pytest.approx(1.64e-4)
    # the benchmark's fleet: 256 tenants, 86,592 datasets, 200 steps,
    # about 2.84 GB a plan, 3.47 ms at the v5e's 819 GB/s
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    assert m.least_seconds((86_592, 4, 5, 200), peaks["TPU v5 lite"]) == \
        pytest.approx(200 * 14_201_088 / 819e9)


def test_cell_hands_the_scan_its_unpadded_shape():
    config = json.loads((BENCH / "configs" / "provider_fleet.json")
                        .read_text())
    mix = json.loads((BENCH / "traffic" / "provider_fleet.shared_quota.json")
                     .read_text())
    config["tenants"] = 5
    cell = run.load_module(BENCH / "configs" / "provider_fleet.py").build(
        config, mix, 11, run.Recorder())
    sizes = [c["datasets"] for c in config["customers"]]
    assert cell.scan_shape == (sum(sizes[t % 4] for t in range(5)), 4, 5, 200)
