"""A profiler trace's device time by raw instruction, under the program's
scopes (the lesson of PR 26: ``data/gather`` was a loop, not the gather; read
the instructions before sizing a change).

    python3 tools/trace_by_instruction.py <trace>.xplane.pb --scope 'l1\\.' --steps 8

One row per (stable name, opcode, result shape): events, milliseconds in
all and a step, longest first, then the sums by stable name. The stable
names are ``chipbench/trace_reduce.py``'s (what the per-layer metrics match),
the opcode and shape are read from the instruction's own text. ``--json``
writes the rows to a file as well. Needs no chip: ``ProfileData`` reads the
file.
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "chipbench"))
import trace_reduce  # noqa: E402


def instruction(text: str):
    """(opcode with the fusion's kind, result shape without layouts) of an
    instruction's text ``%name = <shape or tuple> opcode(operands), ...``."""
    _, eq, rest = text.partition(" = ")
    if not eq:
        return text.split(" ")[0][:40], ""
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.partition("(")[0]
    kind = re.search(r"kind=k(\w+)", text)
    call = re.search(r'custom_call_target="([^"]+)"', text)
    if kind:
        opcode += ":" + kind.group(1).lower()
    elif call:
        opcode += ":" + call.group(1)
    return opcode, re.sub(r"\{[^}]*\}", "", shape)[:60]


def table(path: str, scope: str):
    rows = trace_reduce.load(path)
    meta = trace_reduce.op_metadata(path)
    pattern = re.compile(scope)
    by_row, by_name, busy = {}, {}, 0.0
    for plane, line, text, _, dur in rows:
        m = meta.get(text, {})
        if (not plane.startswith("/device:TPU") or line != trace_reduce.OPS_LINE
                or trace_reduce.is_container(text, m.get("category", ""))):
            continue
        busy += dur / 1e9
        name = trace_reduce.stable_name(m.get("scope", ""), text)
        if not pattern.search(name):
            continue
        key = (name,) + instruction(text)
        n, s = by_row.get(key, (0, 0.0))
        by_row[key] = (n + 1, s + dur / 1e9)
        by_name[name] = by_name.get(name, 0.0) + dur / 1e9
    return by_row, by_name, busy


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--scope", default=".", help="regular expression over stable names")
    ap.add_argument("--steps", type=int, default=1, help="training steps the trace covers")
    ap.add_argument("--least-ms", type=float, default=0.02,
                    help="leave out rows under this many ms a step")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    by_row, by_name, busy = table(args.trace, args.scope)
    ordered = sorted(by_row.items(), key=lambda kv: -kv[1][1])
    print(f"busy {busy:.4f} s over {args.steps} steps; under /{args.scope}/: "
          f"{sum(by_name.values()):.4f} s = {1e3 * sum(by_name.values()) / args.steps:.2f} ms a step")
    print(f"{'stable name':34} {'instruction':28} {'result':30} {'events':>6} {'ms':>9} {'ms/step':>8}")
    for (name, opcode, shape), (n, s) in ordered:
        if 1e3 * s / args.steps >= args.least_ms:
            print(f"{name:34} {opcode:28} {shape:30} {n:6d} {1e3 * s:9.3f} "
                  f"{1e3 * s / args.steps:8.3f}")
    print("by stable name, ms a step:")
    for name, s in sorted(by_name.items(), key=lambda kv: -kv[1]):
        print(f"  {name:40} {1e3 * s / args.steps:8.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"busy_s": busy, "steps": args.steps,
                       "rows": [[*k, n, s] for k, (n, s) in ordered],
                       "by_name": by_name}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
