"""tools/hlo_digests.py, the check a router refactor is held to: the same
checkout lowers to the same digests, and a digest that differs is named."""
import importlib.util
import json
import pathlib
import re

import pytest

_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _tool():
    spec = importlib.util.spec_from_file_location(
        "hlo_digests", _ROOT / "tools" / "hlo_digests.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("match", ["serve:stream_default",
                                   "run:lm_stream"])
def test_hlo_digests_repeat_and_name_a_difference(tmp_path, monkeypatch,
                                                  capsys, match):
    tool = _tool()
    monkeypatch.chdir(_ROOT)
    out = tmp_path / "digests.json"
    assert tool.main(["--match", match, "--out", str(out)]) == 0
    digests = json.loads(out.read_text())
    assert digests and all(k.startswith(match) for k in digests)
    assert all(re.fullmatch("[0-9a-f]{16}", v) for v in digests.values())

    assert tool.main(["--match", match, "--against", str(out)]) == 0
    name = sorted(digests)[0]
    out.write_text(json.dumps(dict(digests, **{name: "0" * 16})))
    capsys.readouterr()
    assert tool.main(["--match", match, "--against", str(out)]) == 1
    assert f"DIFFERS {name}:" in capsys.readouterr().out


def test_strip_locations_keeps_the_ops():
    text = ('%0 = stablehlo.add %a, %b : tensor<f32> loc(#loc3)\n'
            '#loc3 = loc("x.py":1:2)\n'
            '%1 = stablehlo.mul %0, %0 : tensor<f32> '
            'loc(fused[#loc1, "f"(#loc2)])')
    assert _tool().strip_locations(text) == (
        "%0 = stablehlo.add %a, %b : tensor<f32>\n"
        "%1 = stablehlo.mul %0, %0 : tensor<f32>")
