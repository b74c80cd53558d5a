"""The control of each configuration comes out not correct.

The control is the plain reference put in the program's place, computed
in the precision step below the one the configuration states: float8
e4m3 products for the served bfloat16 model, three-pass bfloat16
products for the float32-HIGHEST QR.  At a small size it must read above
the cell's limit while the program, on the same inputs, reads below it.
``bench/calibrate.py limits`` makes the same readings on the chip at the
cells' own sizes.  The serving test runs for every serving configuration
of ``BENCHMARK.json``, at its architecture's ``small(c, control=True)``
widths."""

import numpy as np
import pytest

import harness
from conftest import (SERVE_CONFIGS, small_chat_mix, small_qr_config,
                      small_serve_config)


@pytest.mark.parametrize("config", SERVE_CONFIGS)
def test_serving_control_fails_the_limit(config):
    c = small_serve_config(config, control=True)
    mix = small_chat_mix()
    sc = harness.driver("serve").ServeCell(c, mix, 5)
    sc.warm()
    w = sc.window(5, 2.0)
    g = sc.compare(w, sc.sample(w, 5), control=True)
    limit = c["limits"]["served_gap_max"]
    assert g["gap"].max() <= limit < g["control_gap"].max()


def test_qr_control_fails_the_limit():
    c = small_qr_config(512)
    qc = harness.driver("engine").QRCell(c, 5)
    w = qc.window(5, 0.5, 2)
    e = qc.compare(w["kept"], control=True)
    limit = c["limits"]["r_rel_err_max"]
    assert max(e["r_rel_err"]) <= limit < min(e["control_r_rel_err"])
    assert np.isfinite(e["control_r_rel_err"]).all()
