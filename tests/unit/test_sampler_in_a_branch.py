"""Witness on the COMPILED decode chunk of the benchmark's configurations
(PR 44): the sampler's top-k over the vocabulary lies inside a conditional's
branch, so a chunk whose live rows are all greedy takes the argmax and
nothing else. Ahead-of-time compiles for a described v5e (nothing runs; a
compile that passes is not a chip run), through the benchmark's own helpers
under `tests/perf`, which this file only reads."""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "perf"))

import aot  # noqa: E402  (tests/perf/aot.py)
from test_aot_v5e import topo  # noqa: E402, F401  (the described v5e:2x2)


def _compile(described, name):
    cfg = aot.load_config(name)
    if name.startswith("qwen3-next"):
        import test_aot_qwen3_next as hybrid  # tests/perf

        return hybrid.compile_hybrid_cell(described, cfg, admit=8, bucket=256)
    if name.startswith("sdar"):
        import test_aot_sdar as block  # tests/perf

        return block.compile_block_cell(described, cfg, admit=8, bucket=256)
    return aot.compile_cell(
        described, cfg, admit=8, bucket=256, what=("decode",))


@pytest.fixture(scope="module")
def chunk(topo):  # noqa: F811
    """configuration -> `Program` of its compiled decode chunk, compiled
    once a module."""
    done = {}

    def get(name):
        if name not in done:
            done[name] = Program(_compile(topo, name)["decode_text"])
        return done[name]

    return get


HEAD = re.compile(r"^(?:ENTRY )?%([\w.-]+) \(.*\{\s*$")
CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.-]+)")
CALL_SETS = re.compile(r"(?:branch_computations|called_computations)=\{([^}]*)\}")
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


class Program:
    """An HLO module's text cut into computations, with who calls whom."""

    def __init__(self, text: str):
        self.lines: dict[str, list[str]] = {}
        self.entry = None
        name = None
        for line in text.splitlines():
            head = HEAD.match(line)
            if head:
                name = head.group(1)
                self.lines[name] = []
                if line.startswith("ENTRY"):
                    self.entry = name
            elif line.startswith("}"):
                name = None
            elif name is not None:
                self.lines[name].append(line)
        assert self.entry is not None and len(self.lines) > 10

    @staticmethod
    def _names(group: str) -> list[str]:
        return [n.strip().lstrip("%") for n in group.split(",") if n.strip()]

    def called(self, line: str) -> set[str]:
        names = set(CALLS.findall(line))
        for group in CALL_SETS.findall(line):
            names.update(self._names(group))
        return names & self.lines.keys()

    def branches(self, line: str) -> set[str]:
        """The branch computations of a `conditional` instruction."""
        if " conditional(" not in line:
            return set()
        names = set()
        for group in BRANCHES.findall(line):
            names.update(self._names(group))
        return names & self.lines.keys()

    def reach(self, roots, through_branches: bool) -> set[str]:
        seen, todo = set(), list(roots)
        while todo:
            comp = todo.pop()
            if comp in seen:
                continue
            seen.add(comp)
            for line in self.lines[comp]:
                nxt = self.called(line)
                if not through_branches:
                    nxt -= self.branches(line)
                todo.extend(nxt)
        return seen

    def conditionals(self) -> list[tuple[str, str]]:
        return [(comp, line) for comp, ls in self.lines.items()
                for line in ls if self.branches(line)]

    def holding(self, pattern: str, comps=None) -> dict[str, list[str]]:
        """computation -> its instructions that match `pattern`."""
        rx = re.compile(pattern)
        out = {}
        for comp in self.lines if comps is None else comps:
            hits = [ln.strip()[:200] for ln in self.lines[comp] if rx.search(ln)]
            if hits:
                out[comp] = hits
        return out


# The sampler's candidate pool in the compiled text: the TopK custom call
# (and a sort, should the compiler choose one) under the `sample` scope. A
# routed family's router takes a top-k of its own, under `moe_router`.
POOL = r'(custom_call_target="TopK"| sort\().*op_name="[^"]*/sample/'
CELLS = ("mistral-7b-v5e1", "qwen3-next-80b-a3b-v5e1", "mixtral-8x7b-v5e4",
         "sdar-30b-a3b-v5e1")


def sampler_conditional(prog: Program):
    """(the chunk's one conditional that holds the pool, the computations
    each of its branches reaches)."""
    pool = prog.holding(POOL)
    assert pool, "the chunk holds no top-k under the sample scope"
    around = [
        (line, {b: prog.reach([b], True) for b in prog.branches(line)})
        for _, line in prog.conditionals()
    ]
    around = [(line, arms) for line, arms in around
              if any(set(pool) & comps for comps in arms.values())]
    assert len(around) == 1, [line[:160] for line, _ in around]
    return around[0]


@pytest.mark.parametrize("name", CELLS)
def test_the_pool_is_reached_through_a_branch_only(chunk, name):
    prog = chunk(name)
    pool = prog.holding(POOL)
    # Walked without entering any conditional's branches, the program never
    # comes to the top-k over the vocabulary.
    outside = prog.reach([prog.entry], through_branches=False)
    assert not set(pool) & outside, {c: pool[c] for c in set(pool) & outside}
    line, arms = sampler_conditional(prog)
    assert "/sample/" in line
    with_pool = [b for b, comps in arms.items() if set(pool) & comps]
    without = [b for b in arms if b not in with_pool]
    assert len(with_pool) == len(without) == 1
    # The other branch is the argmax: no top-k, sort, scan or draw in it.
    assert not prog.holding(
        r'TopK| sort\(| reduce-window\(|threefry|rng-bit', arms[without[0]])
    # None nested: no conditional inside either branch.
    inside = set().union(*arms.values())
    assert not [ln for comp, ln in prog.conditionals() if comp in inside]


@pytest.mark.parametrize("name", CELLS)
def test_the_chunk_holds_one_conditional_and_hands_its_predicate_back(chunk, name):
    prog = chunk(name)
    assert len(prog.conditionals()) == 1
    # The scalar leaves the program as an output of its own.
    root = [ln for ln in prog.lines[prog.entry] if "ROOT " in ln]
    assert len(root) == 1 and re.search(r"pred\[\]", root[0]), root


def test_at_four_chips_the_samplers_collectives_lie_in_its_branches(chunk):
    """Mixtral's logits are split over the vocabulary: the argmax gathers a
    value and an index a chip, the pool 64 of each. The predicate is
    replicated, so every chip takes the same branch; no gather of the
    sampler's is left outside them, where it would run either way."""
    prog = chunk("mixtral-8x7b-v5e4")
    gathers = prog.holding(r' all-gather\(.*op_name="[^"]*/sample/')
    assert gathers
    outside = prog.reach([prog.entry], through_branches=False)
    assert not set(gathers) & outside
    _, arms = sampler_conditional(prog)
    per_arm = sorted(
        sum(len(v) for c, v in gathers.items() if c in comps)
        for comps in arms.values())
    assert per_arm == [2, 4], gathers
