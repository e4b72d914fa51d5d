"""Names a profiler trace can tell the step's work apart by: the method's
named scopes in the compiled step (`perturb`, `descent`, `ascent`, `update`,
`cross_entropy`) and the `name=` of every Pallas kernel. Scopes are metadata:
the step's operations are the same with them and without."""
import ast
import contextlib
import pathlib
import re

import jax
import pytest

from repro import optim
from repro.core import MethodConfig
from repro.data import PipelineConfig, TokenPipeline
from repro.engine import FusedExecutor
from repro.models import build_model
from repro.models.config import ModelConfig

KERNELS = pathlib.Path(__file__).resolve().parents[1] / "src/repro/kernels"
CFG = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=16,
                  n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=32,
                  act="silu", norm="nonparam_ln", tie_embeddings=True,
                  remat="dots", compute_dtype="float32")
SCOPES = ("perturb", "descent", "ascent", "update", "cross_entropy")


def _lowered(method: str):
    bundle = build_model(CFG)
    ex = FusedExecutor(bundle.loss_fn,
                       MethodConfig(name=method, rho=0.05,
                                    ascent_fraction=0.5),
                       optim.adamw(1e-3), donate=False)
    state = jax.eval_shape(lambda: ex.init_state(
        bundle.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1)))
    batch = TokenPipeline(CFG, PipelineConfig(
        global_batch=2, seq_len=8,
        ascent_fraction=0.5 if method != "sgd" else 0.0)).peek()
    return ex.lower(state, jax.eval_shape(lambda: batch))


def _scopes(lowered) -> set:
    """The SCOPES some op_name of the compiled step's HLO holds as a
    component, also inside a transformation such as transpose(jvp(ascent))."""
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return {s for s in SCOPES for n in names
            if re.search(rf"(^|[/(]){s}([/)]|$)", n)}


@pytest.mark.parametrize("method,want", [
    ("async_sam", set(SCOPES)),
    ("sam", set(SCOPES)),
    ("sgd", {"descent", "update", "cross_entropy"}),
])
def test_step_carries_the_method_scopes(method, want):
    assert _scopes(_lowered(method)) == want


@pytest.mark.parametrize("method", ["async_sam", "sgd"])
def test_scopes_leave_the_step_operations_as_they_are(method, monkeypatch):
    scoped = _lowered(method).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered(method)
    assert _scopes(bare) == set()
    assert bare.as_text() == scoped


def _pallas_calls(path: pathlib.Path) -> list:
    return [node for node in ast.walk(ast.parse(path.read_text()))
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call"]


@pytest.mark.parametrize("path", sorted(
    p for p in KERNELS.glob("*.py") if _pallas_calls(p)), ids=lambda p: p.stem)
def test_every_pallas_call_is_named(path):
    names = []
    for call in _pallas_calls(path):
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"{path.name}:{call.lineno} has no name="
        assert isinstance(kw["name"], ast.Constant), call.lineno
        names.append(kw["name"].value)
    assert len(set(names)) == len(names)
    # a trace labels an operation by the letters of its HLO name
    assert all(re.fullmatch(r"[a-z_]+", n) for n in names), names
