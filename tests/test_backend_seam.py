"""The batch path's seams: arrows that point one way, and the one table
that knows the model families (``models/backend.py``)."""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "music_analyst_tpu")

# ------------------------------------------------------------------ arrows
#
# Two rules, written once.  (1) The spine is ordered; no package of it
# imports one to its right.  (2) A supporting package imports none of the
# spine's upper four.
SPINE = ("ops", "models", "engines", "serving", "cli")
SUPPORTING = ("utils", "telemetry", "observability", "profiling", "metrics",
              "resilience", "runtime", "parallel", "data")

# Upward imports that stand as named debts (ROADMAP.md "Design queue").
# The list may only shrink: a new pair fails, and so does a pair that is
# no longer in the code.
KNOWN_UPWARD = {
    # the weight store lives under engines/ and is no engine
    ("models/distilbert.py", "engines.checkpoint"),
    ("models/distilbert.py", "engines.wq_cache"),
    ("models/llama.py", "engines.checkpoint"),
    ("models/llama.py", "engines.wq_cache"),
}


def _points_up(package: str, target: str) -> bool:
    if package in SPINE:
        return target in SPINE and SPINE.index(target) > SPINE.index(package)
    return target in SPINE[1:]


def _is_package(module: str) -> bool:
    return os.path.isfile(
        os.path.join(REPO, *module.split("."), "__init__.py"))


def _imported_modules(path: str, module_parts):
    """Every ``music_analyst_tpu`` module a file imports — lazy imports
    inside functions included — as dotted names below the package."""
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: resolve against the file's package
                parent = module_parts[: len(module_parts) - node.level]
                base = ".".join(parent + ([base] if base else []))
            # ``from package import module`` names the module
            names = ([f"{base}.{alias.name}" for alias in node.names]
                     if _is_package(base) else [base])
        else:
            continue
        for name in names:
            if name.startswith("music_analyst_tpu."):
                yield name[len("music_analyst_tpu."):]


def _upward_imports(package: str):
    found = set()
    for directory, _, files in os.walk(os.path.join(PACKAGE, package)):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(directory, name)
            rel = os.path.relpath(path, PACKAGE).replace(os.sep, "/")
            parts = ["music_analyst_tpu"] + rel[: -len(".py")].split("/")
            for module in _imported_modules(path, parts):
                if _points_up(package, module.split(".")[0]):
                    # a module, not a name imported from it
                    while not (_is_package("music_analyst_tpu." + module)
                               or os.path.isfile(os.path.join(
                                   PACKAGE, *module.split(".")) + ".py")):
                        module = module.rsplit(".", 1)[0]
                    found.add((rel, module))
    return found


@pytest.mark.parametrize("package", SPINE + SUPPORTING)
def test_no_import_points_up(package):
    known = {pair for pair in KNOWN_UPWARD
             if pair[0].startswith(package + "/")}
    found = _upward_imports(package)
    assert found - known == set(), "new upward import(s)"
    assert known - found == set(), (
        "no longer in the code: take the pair(s) off KNOWN_UPWARD")


def test_every_package_is_ordered():
    """A new package has to be given its place in the order."""
    packages = {
        name for name in os.listdir(PACKAGE)
        if os.path.isfile(os.path.join(PACKAGE, name, "__init__.py"))
    }
    assert packages == set(SPINE) | set(SUPPORTING)


def test_batch_job_loads_no_serving_module(fixture_csv, tmp_path):
    """``engines.sentiment``, a built backend and a whole job with its
    manifest pull in nothing of the serving package (it was 8 modules and
    ``decode_loop.py`` a job)."""
    code = (
        "import sys\n"
        "import music_analyst_tpu.engines.sentiment as s\n"
        "s.get_backend('mock')\n"
        "s.run_sentiment(sys.argv[1], mock=True, output_dir=sys.argv[2],"
        " quiet=True)\n"
        "print([m for m in sys.modules"
        " if m.startswith('music_analyst_tpu.serving')])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code, str(fixture_csv), str(tmp_path)],
        cwd=REPO, capture_output=True,
        text=True, check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    ).stdout
    assert out.strip() == "[]"


# --------------------------------------------------------------- the table

from music_analyst_tpu.models import backend as seam  # noqa: E402


@pytest.mark.parametrize("model, mock, takes_mesh", [
    ("distilbert", False, True),
    ("distilbert-tiny-int8", False, True),
    ("llama3-tiny", False, True),
    ("kanana-tiny", False, True),
    ("mock", False, False),
    ("distilbert", True, False),  # --mock wins
    ("ollama:llama3", False, False),
    ("no-such-model", False, False),
])
def test_mesh_capability_gate(model, mock, takes_mesh):
    """mesh= must reach only the on-device model families; the keyword
    kernel and the Ollama HTTP passthrough take no mesh kwarg."""
    assert seam.family_takes(model, mock, "mesh") is takes_mesh


@pytest.mark.parametrize("model, family, buckets, weight_quant, env", [
    ("mock", "mock", False, False, None),
    ("ollama", "ollama", False, False, None),
    ("ollama:phi3", "ollama", False, False, None),
    ("distilbert-tiny-packed", "distilbert", True, True,
     "MUSICAAL_DISTILBERT_CKPT"),
    ("llama3-8b", "decoder", False, True, "MUSICAAL_LLAMA_CKPT"),
    ("kanana-2-30b-a3b", "decoder", False, True, "MUSICAAL_LLAMA_CKPT"),
])
def test_family_rows(model, family, buckets, weight_quant, env):
    row = seam.family_of(model)
    assert (row.name, row.length_buckets, row.weight_quant,
            row.checkpoint_env) == (family, buckets, weight_quant, env)


def test_unknown_model_names_the_families():
    with pytest.raises(ValueError, match=(
            "unknown model 'bert': expected 'mock', 'distilbert\\*', "
            "'llama\\*', 'ai\\*', 'granite\\*', 'jamba\\*', 'kanana\\*', "
            "'laguna\\*', 'ling\\*' or 'sdar\\*'")):
        seam.get_backend("bert")


def test_every_decoder_preset_is_a_model_name():
    """Every preset ``models/llama.py`` builds, in code or from a file of
    ``models/presets/``, resolves to the decoder's row."""
    from music_analyst_tpu.models.llama import PRESETS

    files = sorted(name[: -len(".json")]
                   for name in os.listdir(seam.PRESET_DIR))
    assert files and set(files) <= set(PRESETS)
    for name in PRESETS:
        assert seam.family_of(name).name == "decoder", name
        assert seam.family_of(name + "-int8").name == "decoder", name


def test_a_new_preset_file_is_a_model_with_no_other_edit(
        tmp_path, monkeypatch):
    """``models/presets/<new>.json`` and nothing else: ``get_backend``
    builds it."""
    from music_analyst_tpu.models import llama

    shutil.copy(os.path.join(seam.PRESET_DIR, "kanana-tiny.json"),
                tmp_path / "brandnew-tiny.json")
    with pytest.raises(ValueError, match="unknown model"):
        seam.family_of("brandnew-tiny")
    monkeypatch.setattr(seam, "PRESET_DIR", str(tmp_path))
    monkeypatch.setattr(llama, "PRESETS", llama.presets())
    assert seam.family_of("brandnew-tiny").name == "decoder"
    clf = seam.get_backend("brandnew-tiny", max_prompt_len=64)
    assert clf.config == llama.LlamaConfig.from_preset_file(
        os.path.join(seam.PRESET_DIR, "brandnew-tiny.json"))
    assert clf.classify_batch(["la la love", ""])[1] == "Neutral"


def test_weight_quant_with_experts_is_the_models_own_refusal():
    """Not "does not support it" by accident of a prefix: the decoder
    takes ``weight_quant``, and this configuration's experts refuse it."""
    with pytest.raises(ValueError, match="MoE expert stacks"):
        seam.get_backend("kanana-tiny", weight_quant="int8")
    with pytest.raises(ValueError, match="does not support it"):
        seam.get_backend("mock", weight_quant="int8")
    with pytest.raises(ValueError, match="does not support it"):
        seam.get_backend("distilbert", mock=True, weight_quant="int8")


@pytest.mark.parametrize("argv, message", [
    (["sentiment", "x.csv", "--model", "llama3-tiny",
      "--length-buckets", "32,64"],
     "--length-buckets requires --model distilbert[-*] "
     "(not --mock or decoder models)"),
    (["sentiment", "x.csv", "--mock", "--weight-quant", "int8"],
     "--weight-quant requires an on-device model family "
     "(distilbert[-*] or llama[3*])"),
    (["serve", "--stdio", "--model", "ollama:llama3",
      "--weight-quant", "int8"],
     "--weight-quant requires an on-device model family "
     "(distilbert[-*] or llama[3*])"),
])
def test_cli_usage_errors_keep_their_text(argv, message, capsys):
    from music_analyst_tpu.cli.main import main

    with pytest.raises(SystemExit) as exit_info:
        main(argv)
    assert exit_info.value.code == 2
    assert message in capsys.readouterr().err


def test_decode_runtime_refusal_is_the_one_answer():
    """A backend that runs no decoder says so; the server and the
    scheduler ask nothing else of it."""
    from music_analyst_tpu.models.mock import MockKeywordClassifier
    from music_analyst_tpu.serving.decode_runtime import (
        decode_runtime_refusal,
        slot_runtime,
    )

    mock = MockKeywordClassifier()
    assert "no decoder" in decode_runtime_refusal(mock, "slot")
    assert "no decoder" in decode_runtime_refusal(object(), "paged")
    with pytest.raises(NotImplementedError, match="no decoder"):
        slot_runtime(mock)


def test_manifest_names_the_seam_spans(fixture_csv, tmp_path):
    """``backend_init`` around ``serve.load``: names and order as before
    the residency moved below the engines."""
    from music_analyst_tpu.engines.sentiment import run_sentiment

    run_sentiment(str(fixture_csv), mock=True, output_dir=str(tmp_path),
                  quiet=True)
    spans = [json.loads(line) for line in
             (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    spans = {e["name"]: e for e in spans if e.get("type") == "span"}
    assert spans["serve.load"]["parent_id"] == spans["backend_init"]["span_id"]
