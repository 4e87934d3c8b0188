"""What one ``edl_serve_block`` holds, from a traced benchmark run: the
block of median length on the ``XLA Modules`` line, and the self time of
the operations inside it by phase (``attn`` split into the decode kernel
and everything else under it: the projections' weights), with the
heaviest operations by name. PERF.md section 5's split of a block.

Run after ``python3 -m benchmark.run --workload <cell> --trace 1``:
``python3 scripts/exp_block_split.py .bench_trace/<cell>``
"""

import collections
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reduce import program, trace  # noqa: E402

KERNEL = re.compile(r"edl_decode_attn|custom-call")


def main(trace_dir: str) -> None:
    planes = program.load(trace.find_xplane(trace_dir) or trace_dir)
    modules = program.device_lines(planes, trace.MODULES_LINE)[0]
    blocks = sorted(
        (e - s, s, e) for name, s, e, _ in modules
        if program.program_name(name) == program.BLOCK_PROGRAM)
    ns, start, end = blocks[len(blocks) // 2]
    print(f"{len(blocks)} blocks, median {ns / 1e6:.3f} ms "
          f"(mean {statistics.mean(b[0] for b in blocks) / 1e6:.3f})")
    ops = [ev for ev in program.device_lines(planes, trace.OPS_LINE)[0]
           if start <= ev[1] and ev[2] <= end]
    by_phase, by_op, stack = collections.Counter(), collections.Counter(), []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            keys, _, own = stack.pop()
            by_phase[keys[0]] += own
            by_op[keys] += own

    for name, s, e, stats in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        phase = program.scope_of(stats.get(program.OP_NAME_STAT, "")) or "-"
        if phase == "attn" and KERNEL.search(name):
            phase = "attn.kernel"
        # an event's name is its whole instruction: `%fusion.12 = bf16[..`
        op = re.sub(r"[.\d]+$", "", name.split(" = ", 1)[0].lstrip("%"))
        stack.append([(phase, op), e, e - s])
    close(1 << 62)
    for phase, own in by_phase.most_common():
        print(f"  {phase:12s} {own / 1e6:7.3f} ms  {100 * own / ns:5.1f}%")
    print(f"  {'busy':12s} {sum(by_phase.values()) / 1e6:7.3f} ms")
    for (phase, name), own in by_op.most_common(12):
        print(f"    {own / 1e6:7.3f} ms  {phase:12s} {name}")


if __name__ == "__main__":
    main(sys.argv[1])
