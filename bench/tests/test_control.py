"""The control comes out as not correct where the program does not: at a
tiny size on the CPU, over three seeds, the fp8 control's smallest reading
of one of the compared numbers lies above the program's largest by the
factor that a limit between them needs (three), and above the tiny cell's
limit, which the program stays under."""
import json

from bench import check, control
from conftest import CELLS


def test_control_fails_where_the_program_passes(tiny_root):
    lines = []
    s = control.readings("tiny.closed", [11, 12, 13], 1.0, root=tiny_root,
                         require_tpu=False, out=lines.append)
    limits = CELLS["tiny.closed"]["check"]["limits"]
    per_seed = [json.loads(x) for x in lines[:-1]]
    assert len(per_seed) == 3 and all(p["program"]["tokens"] > 0 for p in per_seed)
    assert all(s["lower"][k] <= limits[k] for k in check.NAMES)
    assert any(s["upper"][k] > max(3 * s["lower"][k], limits[k]) for k in check.NAMES), s
