"""Device scopes and host spans on the profiler's clock (DESIGN.md §15):
the round's phases are named in the compiled program of every in-memory
engine, only names of ``repro.obs.SCOPES`` can be opened, and the FL
loop's host spans land in a captured device trace."""

from __future__ import annotations

import glob
import re

import jax
import pytest

from repro.api import EngineSpec, FediACConfig, aggregate_round
from repro.obs import SCOPES, RecordingProbe, scope

PHASES = ("vote", "consensus", "phase2")
# instructions that move or hold values, or hold other computations: no
# work of a phase of their own
STRUCTURAL = ("parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "copy", "while", "conditional", "call")
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*)$")


def _opcode(rhs: str) -> str:
    depth = 0
    for i, c in enumerate(rhs):   # skip the result shape, which may hold spaces
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            return rhs[i:].strip().split("(", 1)[0]
    return rhs


def executed_ops(hlo: str) -> list:
    """``(opcode, op_name)`` of every instruction of the computations that
    run as ops (the entry and the bodies and conditions of its loops,
    conditionals and calls), not of fused or reducer computations."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{\s*$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and _INSTR.match(line):
            comps[cur].append(line)
    ops, todo, seen = [], [entry], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            op = _opcode(_INSTR.match(line).group(2))
            name = re.search(r'op_name="([^"]*)"', line)
            ops.append((op, name.group(1) if name else ""))
            if op in ("while", "conditional", "call"):
                todo += re.findall(
                    r"(?:condition|body|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)", line)
                branches = re.search(r"branch_computations=\{([^}]*)\}", line)
                if branches:
                    todo += [b.strip().lstrip("%")
                             for b in branches.group(1).split(",")]
    return ops


def phase_of(op_name: str) -> str | None:
    words = re.findall(r"[\w.<>-]+", op_name)
    return next((w for w in reversed(words) if w in SCOPES), None)


ENGINES = [EngineSpec("stream", chunk=4096), EngineSpec("monolithic"),
           EngineSpec("sharded", devices=1)]
MODES = [("threshold", "block"), ("topk", "topk")]


@pytest.mark.parametrize("mode", MODES, ids=lambda m: "/".join(m))
@pytest.mark.parametrize("engine", ENGINES, ids=lambda e: e.name)
def test_round_phases_are_scoped_in_the_compiled_program(engine, mode):
    vote_mode, compact_mode = mode
    cfg = FediACConfig(k_frac=0.05, bits=12, capacity_frac=0.05,
                       vote_mode=vote_mode, compact_mode=compact_mode,
                       block_size=1024, engine=engine)
    u = jax.random.normal(jax.random.PRNGKey(0), (4, 4096 * 4 + 1000))
    lowered = jax.jit(lambda u, k: aggregate_round(u, cfg, k)[:3]).lower(
        u, jax.random.PRNGKey(1))
    hlo = lowered.compile().as_text()
    ops = executed_ops(hlo)
    found = {phase_of(name) for _, name in ops}
    assert set(PHASES) <= found
    if engine.name == "stream":
        # every op the program made (the compiler's own carry no op_name)
        # lies in a phase
        stray = [(op, name) for op, name in ops
                 if name and op not in STRUCTURAL and phase_of(name) is None]
        assert stray == []
    # the fold is named inside phase 2, and nowhere else (a reducer's body
    # carries the end of the path only: ``register_fold/reduce_sum``)
    folds = set(re.findall(r'op_name="(jit\([^"]*register_fold[^"]*)"', hlo))
    assert folds
    assert all(re.search(r"phase2\b.*register_fold", f) for f in folds)


def test_scope_refuses_an_unknown_name():
    with scope("vote"):
        pass
    with pytest.raises(ValueError, match="unknown device scope 'phase3'"):
        scope("phase3")


def test_fl_loop_host_spans_land_in_the_profiler_trace(tmp_path):
    """Two rounds of the lossy-network example's task, recorded by a
    probe under a profiler trace: the loop's spans are host events of the
    captured ``.xplane.pb``, and the local round's program, which the trace
    keeps with its op names, runs in the ``local_train`` scope."""
    from repro.sweep.runner import run_cell_sequential
    from repro.sweep.spec import ScenarioSpec
    spec = ScenarioSpec(name="memory", algorithm="fediac", a=2, bits=12,
                        n_clients=4, rounds=2, local_steps=3, dist="noniid",
                        beta=0.5, data_n=600, data_dim=32, data_classes=10,
                        test_frac=0.2)
    with jax.profiler.trace(str(tmp_path)), RecordingProbe() as probe:
        run_cell_sequential(spec, 0, probe=probe)
    spans = [r["name"] for r in probe.tracer.records if r["type"] == "span"]
    assert spans.count("round") == 2
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = [e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    for name in ("round", "local-train", "aggregate"):
        assert host.count(name) == 2, name
    with open(path, "rb") as f:
        assert re.search(rb"jit\(local_train\)/local_train/", f.read())
