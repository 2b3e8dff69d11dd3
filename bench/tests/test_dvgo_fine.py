"""The comparison sees DVGO's fine-stage mathematics: with a fault planted
in the program's decoder, the tiny ``dvgo-fine`` cell comes out not
correct. The faults are slips a port of DVGO could make: the density
shift taken as ``ln(alpha_init)`` for ``ln(1/(1-alpha_init) - 1)``, and
the view encoding's sines and cosines laid out axis-fastest where DVGO
varies the frequency fastest."""
from __future__ import annotations

import numpy as np
import pytest

from bench_cells import tiny_cell


def shift_from_alpha(monkeypatch):
    from repro.nerf import mlp

    monkeypatch.setattr(mlp.DecoderCfg, "density_shift", property(
        lambda self: float(np.log(self.alpha_init))))


def pe_axis_fastest(monkeypatch):
    from repro.kernels import ops

    rgbnet = ops.dvgo_rgbnet

    def faulty(feats, dirs, params, cfg, **kw):
        k, pe = feats.shape[1] - 1, cfg.viewbase_pe
        # entry a*pe + f of the sines (and cosines) takes the weights of
        # entry f*3 + a
        swap = np.array([f * 3 + a for a in range(3) for f in range(pe)])
        rows = np.concatenate([np.arange(k + 3), k + 3 + swap,
                               k + 3 + 3 * pe + swap])
        return rgbnet(feats, dirs, dict(params, w1=params["w1"][rows]), cfg,
                      **kw)

    monkeypatch.setattr(ops, "dvgo_rgbnet", faulty)


FAULTS = {"density_shift": shift_from_alpha, "pe_order": pe_axis_fastest}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_decoder_fault_is_not_correct(fault, monkeypatch):
    import run_cell

    FAULTS[fault](monkeypatch)
    c = tiny_cell("dvgo-fine", "preview")
    r = run_cell.run(c, 2**31 + 5, 2.0, trace=False, require_chip=False)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["frame_err_p99"]["value"] \
        > r["checks"]["frame_err_p99"]["limit"]
