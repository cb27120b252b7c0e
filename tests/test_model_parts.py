"""The seam between the decoders and what they share: a model's file
imports ``models/parts.py`` and ``models/transformer.py`` from
``models/``, never another model's file; the metrics registry reaches
the models through ``parts`` alone; and the one registry of counters
hands back what the benchmark's runners read."""

import ast
import gc
import pathlib

import numpy as np

import mxtpu as mx
from mxtpu.base import MXTPUError
from mxtpu.models import kimi_linear, lfm2_moe, parts
from mxtpu.observability.metrics import get_registry

MXTPU = pathlib.Path(mx.__file__).parent
MODELS = MXTPU / "models"
#: modules of ``models/`` that hold no model of their own: the decoders'
#: parts, the transformer zoo with its Switch expert layer, and sampling
SHARED = {"parts", "transformer", "moe", "sampler"}


def _imported_modules(path):
    """(module, names) of each import in the file at ``path``: the
    module as a dotted path under ``mxtpu`` where it is relative."""
    package = path.relative_to(MXTPU.parent).with_suffix("").parts[:-1]
    out = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            base = list(package[:len(package) - node.level + 1]) \
                if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            names = [a.name for a in node.names]
            out.append((module, names))
            out.extend((module + "." + n, []) for n in names)
        elif isinstance(node, ast.Import):
            out.extend((a.name, []) for a in node.names)
    return out


def _models_named(path):
    """The modules of ``models/`` the file at ``path`` imports."""
    return {module.split(".")[2] for module, _ in _imported_modules(path)
            if module.startswith("mxtpu.models.")}


def test_no_model_file_imports_another_model_and_metrics_only_parts():
    files = sorted(p for p in MODELS.glob("*.py") if p.stem != "__init__")
    models = {p.stem for p in files} - SHARED
    assert {"kimi_linear", "glm4_moe_lite", "keye_vl", "lfm2_moe",
            "smallthinker"} <= models
    for path in files:
        named = _models_named(path) - {path.stem}
        assert not named & models, (path.name, sorted(named & models))
        if path.stem in models:
            assert named <= {"parts", "transformer"}, (path.name, named)
    metrics = MXTPU / "observability" / "metrics.py"
    assert _models_named(metrics) == {"parts"}
    for path in sorted(MXTPU.rglob("*.py")):
        for module, names in _imported_modules(path):
            assert not {"_head_cross_entropy", "_dense"} & set(names), \
                (path, module)


def test_one_registry_reads_the_expert_loads_and_the_convolutions():
    share = parts.ExpertShare(8, 12, 6, 2, held=(2, 3), num_shared=0,
                              prefix="seam_moe_")
    conv = lfm2_moe.ShortConv(8, prefix="seam_conv_")
    assert "seam_moe" not in kimi_linear.expert_loads()    # not built yet
    before = lfm2_moe.conv_counts().get("positions", 0)
    x = mx.nd.array(np.random.default_rng(0).normal(size=(2, 5, 8)),
                    dtype="float32")
    for block in (share, conv):
        block.initialize(mx.init.Xavier())
        assert block(x).shape == x.shape

    load = kimi_linear.expert_loads()["seam_moe"]
    assert sorted(load) == ["elsewhere", "elsewhere_sum", "held",
                            "held_sum"]
    assert len(load["held"]) == len(load["held_sum"]) == 3
    assert sum(load["held"]) + load["elsewhere"] == 2 * 5 * 2
    assert isinstance(load["elsewhere"], int)
    assert isinstance(load["elsewhere_sum"], float)
    assert lfm2_moe.conv_counts()["positions"] - before == 2 * 5

    snap = get_registry().snapshot(["moe", "conv"])
    for i, pairs in enumerate(load["held"]):
        assert snap["moe.seam_moe.held.%d" % i] == pairs
        assert snap["moe.seam_moe.held_sum.%d" % i] == pairs
    assert snap["moe.seam_moe.elsewhere"] == load["elsewhere"]
    assert snap["moe.seam_moe.elsewhere_sum"] == load["elsewhere_sum"]
    assert snap["conv.positions"] == lfm2_moe.conv_counts()["positions"]
    assert not [k for k in snap if k.endswith("source_error")]


def test_the_registry_sums_the_summed_keeps_the_own_and_skips_the_unset():
    class Counted:
        def __init__(self, summed, own):
            self._read = None if summed is None else (summed, own)
            parts.count_under("seam", self)

        def counters(self):
            if self._read is None:
                raise MXTPUError("not initialised yet")
            return self._read

    blocks = [Counted({"n": 1, "x": 0.5}, {"a.kept": 2.0}),
              Counted({"n": 3}, {"b.kept": 4.0}), Counted(None, None)]
    assert parts.read_counters("seam") == {"n": 4, "x": 0.5, "a.kept": 2.0,
                                           "b.kept": 4.0}
    assert parts.read_counters("nothing counted here") == {}
    del blocks
    gc.collect()
    assert parts.read_counters("seam") == {}        # the dead are gone
