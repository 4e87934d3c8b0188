"""Where a traced cell's device time went, operation by operation: for
each program of the trace (``edl_serve_block``, ``edl_serve_prefill_*``,
...) the self time of its operations grouped by the scopes they stand
under and the operation's own name, heaviest first, as milliseconds a
run of the program. Read after ``python3 -m benchmark.run --workload
<cell> --trace 1`` in the same checkout:

    PYTHONPATH=. python3 scripts/exp_trace_ops.py .bench_trace/<cell> [top]
"""

import collections
import re
import sys

from benchmark.reduce import program, trace


def main() -> None:
    path = trace.find_xplane(sys.argv[1]) or sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    planes = program.load(path)
    ops = program.device_lines(planes, trace.OPS_LINE)[0]
    modules = program.device_lines(planes, trace.MODULES_LINE)[0]
    runs = collections.Counter(program.program_name(m[0]) for m in modules)
    inside = sorted((s, e, program.program_name(n)) for n, s, e, _ in modules)
    by = collections.defaultdict(lambda: collections.Counter())
    stack = []  # [key, end, self ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            key, _, own = stack.pop()
            by[key[0]][key[1:]] += own

    for name, s, e, stats in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        prog = next((p for a, b, p in inside if a <= s and e <= b), "?")
        parts = [p for p in stats.get(program.OP_NAME_STAT, "")
                 .rstrip(":").split("/")
                 if not p.startswith(("jit(", "while", "body", "closed_call",
                                      "cond", "branch"))]
        op = re.sub(r"[.\d]+$", "", name.split(" = ")[0].lstrip("%"))
        stack.append([(prog, "/".join(parts[:-1][-3:]), op), e, e - s])
    close(1 << 62)
    for prog, rows in sorted(by.items()):
        n = max(runs.get(prog, 1), 1)
        total = sum(rows.values())
        print(f"== {prog}: {n} runs, {total / n / 1e6:.3f} ms of operations "
              f"a run")
        scopes = collections.Counter()
        for (scope, op), ns in rows.items():
            scopes[scope] += ns
        for scope, ns in scopes.most_common(top):
            print(f"   {ns / n / 1e6:9.3f} ms  {100 * ns / total:5.1f}%  "
                  f"[{scope}]")
        print("   -- by operation")
        for (scope, op), ns in rows.most_common(top):
            print(f"   {ns / n / 1e6:9.3f} ms  {100 * ns / total:5.1f}%  "
                  f"[{scope}] {op}")


if __name__ == "__main__":
    main()
