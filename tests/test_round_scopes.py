"""The federated round names its stages in the compiled program.

``core/engine.py`` wraps each stage of the round in a ``jax.named_scope``
(``fl.sample``, ``fl.down``, ``fl.train``, ``fl.up``, ``fl.aggregate``)
and the client optimizer in ``fl.opt``. A scope lands in each
instruction's ``metadata={op_name=...}`` of the optimized HLO, which is
what a device trace is joined with to give each stage its device time.
Here a tiny round is lowered and compiled for the CPU with the jnp
kernel backend."""
import re

import jax
import pytest

from repro import optim
from repro.core.engine import FedConfig, RoundEngine
from repro.core.qat import QATConfig
from repro.data import partition_iid, synthetic_classification
from repro.models import small

STAGES = ("fl.sample", "fl.down", "fl.train", "fl.up", "fl.aggregate")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"calls=%([\w.\-]+)")
# What may run outside every stage: the metrics' mean of the cohort's
# losses (a reduce and a divide) and XLA:CPU's wrappers of one op that
# its own passes made and gave no metadata (``wrapped_*``).
UNSCOPED_OK = ("jit(round_fn)/div", "jit(round_fn)/reduce_sum")


@pytest.fixture(scope="module", params=["local", "sharded", "fed2d"])
def round_hlo(request):
    """The optimized HLO of a round of an MLP: AdamW clients, E4M3 QAT,
    stochastic E4M3 wire, mean aggregation; built by each of the three
    round builders (the sharded ones on a mesh of one device)."""
    from repro.launch.mesh import make_client_mesh, make_fed_mesh

    d, n_classes, k = 16, 4, 6
    x, y = synthetic_classification(0, 600, d=d, n_classes=n_classes)
    cx, cy, nk = partition_iid(x, y, k=k, seed=0)
    init, apply = small.REGISTRY["mlp"]
    params = init(jax.random.PRNGKey(0), d_in=d, n_classes=n_classes)
    placement = {"local": {},
                 "sharded": dict(mesh=make_client_mesh(1)),
                 "fed2d": dict(mesh=make_fed_mesh(1, 1), model_axis="fsdp")}
    cfg = FedConfig(n_clients=k, participation=0.5, local_steps=2,
                    batch_size=8, comm_mode="rand", qat=QATConfig(),
                    **placement[request.param])
    eng = RoundEngine(small.make_loss(apply),
                      optim.adamw(1e-3, weight_decay=0.01), cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_KERNEL_BACKEND", "jnp")
        return jax.jit(eng.round_fn).lower(
            eng.init(params), cx, cy, nk, jax.random.PRNGKey(1)
        ).compile().as_text()


def _instructions(text):
    """(computation, instruction, opcode, op_name, called computation)."""
    comp = None
    for line in text.splitlines():
        c = COMP.match(line)
        if c:
            comp = c.group(1)
            continue
        m = INSTR.match(line)
        if m and comp is not None:
            on, calls = OP_NAME.search(line), CALLS.search(line)
            yield (comp, m.group(1), m.group(2), on.group(1) if on else "",
                   calls.group(1) if calls else None)


def test_every_stage_scope_reaches_the_optimized_hlo(round_hlo):
    names = {on for *_, on, _ in _instructions(round_hlo)}
    parts = {p for on in names for p in on.split("/")}
    assert set(STAGES) <= parts
    assert "fl.opt" in parts
    # the optimizer runs inside the clients' training, never outside it
    opt = [on for on in names if "/fl.opt/" in on]
    assert opt and all("/fl.train/" in on for on in opt)


def test_the_round_work_carries_a_stage(round_hlo):
    instrs = list(_instructions(round_hlo))
    by_comp = {}
    for comp, _, _, on, _ in instrs:
        by_comp.setdefault(comp, []).append(on)
    missing = []
    for comp, name, op, on, calls in instrs:
        if op not in ("fusion", "dot", "custom-call"):
            continue
        if not on and calls:       # a fusion: its fused computation's
            on = next((o for o in reversed(by_comp.get(calls, [])) if o), "")
        if "/fl." in on or on in UNSCOPED_OK:
            continue
        if not on and name.startswith("wrapped_"):
            continue
        missing.append((name, on))
    assert not missing, missing
