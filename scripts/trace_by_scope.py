"""Device seconds of a traced slice by `jax.named_scope`.

A trace names device operations as the compiled program does (`fusion.123`),
the result line's `breakdown` keeps the ten largest, and a step's small
operations are below them. This maps every operation of the programs that ran
WHOLE inside the slice to the innermost scope in its instruction's `op_name`
in the compiled text, and sums (PERF.md section 5, the by-scope table, PR 47).

On the chip, in ONE command (the trace and the dump do not come back):

    XLA_FLAGS="--xla_dump_to=/tmp/xdump --xla_dump_hlo_as_text \\
        --xla_dump_hlo_module_re=.*decode_chunk.*" \\
    PERF_KEEP_TRACE_EVENTS=/tmp/events.json python3 perf/run.py \\
        --workload <cell> --seed <n> --seconds 40 --trace 1
    python3 scripts/trace_by_scope.py /tmp/events.json \\
        /tmp/xdump/*decode_chunk*after_optimizations.txt decode_chunk 8

The last two arguments: a regex of the program's name in the trace
(`jit__decode_chunk`) and the steps one program runs (`decode_chunk`), which
the seconds are divided by. `--list SCOPE` also prints that scope's operations.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

SCOPES = (
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
    "moe_ffn", "gdn_proj", "gdn_conv", "gdn_update", "gdn_out",
    "kda_proj", "kda_conv", "kda_update", "kda_scan", "kda_out", "mla_proj",
    "mla_decode", "mla_prefill", "latent_page_write",
    "gated_attention", "attn_window", "attn_global", "paged_attention",
    "kv_page_write", "qk_norm", "qkv", "layer_finish", "dense_ffn", "mlp",
    "lm_head", "sample", "block_commit",
)
SCOPE = re.compile(r"/(%s)(?=/|$)" % "|".join(SCOPES))


def scopes_from_text(text: str) -> dict[str, tuple[str, str]]:
    """instruction name -> (innermost known scope or "rest", opcode)."""
    out = {}
    for line in text.splitlines():
        inst = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\([^=]*?\)|\S+) ([\w\-]+)\(", line)
        if not inst:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        found = SCOPE.findall(name.group(1)) if name else []
        out[inst.group(1)] = (found[-1] if found else "rest", inst.group(2))
    return out


def by_scope(program: dict, names: dict) -> dict[str, dict]:
    """scope (`:kernel` for its custom calls) -> {"count", "total_s", "ops"}
    over one entry of `trace_reduce.summarize(...)["ops_in"]`."""
    table: dict[str, dict] = collections.defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "ops": []})
    for name, op in program["ops"].items():
        scope, opcode = names.get(name.split(" ")[0], ("unmapped", "?"))
        row = table[scope + (":kernel" if opcode == "custom-call" else "")]
        row["count"] += op["count"]
        row["total_s"] += op["total_s"]
        row["ops"].append((name, opcode, op["count"], op["total_s"]))
    return dict(table)


def main(argv: list[str]) -> int:
    events_path, text_path, module, steps = argv[:4]
    listed = argv[argv.index("--list") + 1] if "--list" in argv else None
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf"))
    import trace_reduce

    with open(events_path) as f:
        programs = trace_reduce.summarize(json.load(f))["ops_in"]
    with open(text_path) as f:
        names = scopes_from_text(f.read())
    for mod, program in programs.items():
        if not re.search(module, mod):
            continue
        n = program["count"] * int(steps)
        table = by_scope(program, names)
        print(f"{mod}: {program['count']} whole programs, "
              f"{program['total_s'] / n * 1e3:.3f} ms a step; operations "
              f"{sum(r['total_s'] for r in table.values()) / n * 1e3:.3f}")
        for scope, row in sorted(table.items(), key=lambda kv: -kv[1]["total_s"]):
            print(f"  {scope:28s} {row['total_s'] / n * 1e3:8.3f} ms a step "
                  f"{row['count'] / n:8.1f} operations a step")
            if listed and scope.startswith(listed):
                for name, opcode, count, total in sorted(row["ops"], key=lambda o: -o[3]):
                    print(f"      {name:46s} {opcode:14s} {count / n:7.2f} a step "
                          f"{total / count * 1e6:8.2f} us each")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
