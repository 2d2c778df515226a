"""The control (the reference, one precision step down, in the
program's place) comes out not correct, at a tiny size on the CPU."""
import pytest

import check
import control
import gen

CELLS = ["tafeng.ingest", "valuedshopper.forget", "tafeng.serve"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_number(spec, cell):
    cfg, traffic, _, _ = spec(cell)
    seconds = 2.0
    hist = gen.histories(cfg, 5)
    sched = gen.schedule(cfg, traffic, hist, 5, traffic["warm_s"] + seconds)
    prog = control.control_program(cfg, traffic, hist, sched, 5, seconds)
    numbers = check.compare(cfg, traffic, hist, sched, prog)
    assert any(n["value"] > n["limit"] for n in numbers.values()), numbers
    if "read_rate" in traffic:
        # the reads' own number fails too, not only the state's
        assert numbers["read_err"]["value"] > numbers["read_err"]["limit"]
