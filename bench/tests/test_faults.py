"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, reference, comparison)
on the CPU with ``repro.api.run`` wrapped so that every answer is broken
where it is produced.  The cell's own limits judge it.  The faults a
simulated iteration can have: an answer altered (one flow's completion
time), and half of the flows left out of the answer, whose error over the
rest would still look fine.  A state left unchanged and an exchange
between chips left out belong to training on several chips; no cell has
them.
"""
import json
import time

import pytest
from conftest import ROOT

from bench.harness.cell import run_cell
from bench.harness.spec import Cell


def small_cell(config, mix, limits_of) -> Cell:
    return Cell(
        name=f"{config['name']}.{mix}", chips=1, config=config,
        mix=json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                       .read_text()),
        limits=json.loads((ROOT / "bench" / "limits" / f"{limits_of}.json")
                          .read_text()),
        end_to_end=[], per_layer=[], readers={})


def altered(res):
    fid = min(res.fcts)
    res.fcts[fid] *= 1.5
    return res


def halved(res):
    res.fcts = {k: v for i, (k, v) in enumerate(sorted(res.fcts.items()))
                if i % 2 == 0}
    return res


def sound(res):
    return res


MIXES = [("wormhole-cold", "gpt13b-128.wormhole"),
         ("hybrid-auto", "gpt13b-128.hybrid"),
         ("fluid-iter", "gpt13b-128.fluid"),
         ("fluid-iter", "moe8x13b-128.fluid")]


@pytest.mark.parametrize("fault,correct", [(sound, True), (altered, False),
                                           (halved, False)])
@pytest.mark.parametrize("mix,limits_of", MIXES)
def test_broken_answers_are_not_correct(small_config, tmp_path, mix,
                                        limits_of, fault, correct):
    from repro.api import run

    def broken(scn, **opts):
        return fault(run(scn, **opts))

    result, checks = run_cell(
        small_cell(small_config, mix, limits_of), seed=2**31 + 11,
        seconds=0, traced=False, rehearse=True,
        t_start=time.perf_counter(), profile_dir=tmp_path, api_run=broken)
    assert result["correct"] is correct
    assert result["failed"] == (0 if correct else result["attempted"])
    assert len(checks) == len(result["checks"])
