#!/bin/bash
# Style / hygiene gate — port of the reference's CI style check
# (reference: .tools/check_style.sh + .pre-commit-config.yaml: go-fmt,
# go-vet, go-lint excluding generated code). Uses only the baked-in
# toolchain: byte-compile every Python file and reject debugger
# leftovers and tabs in Python source.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== byte-compile =="
# not tests/benchmark/later_pr/: test_benchmark_files.py copies that tree
# file by file, and a __pycache__ left in it fails the copy
python -m compileall -q -x 'tests/benchmark/later_pr/' \
    edl_tpu tests examples bench.py __graft_entry__.py

echo "== debugger / print leftovers =="
if grep -rn "breakpoint()\|pdb.set_trace" edl_tpu/ --include='*.py'; then
    echo "debugger statements found" >&2; exit 1
fi

echo "== no tabs in python =="
if grep -rlP '\t' edl_tpu/ tests/ --include='*.py'; then
    echo "tabs found in python source" >&2; exit 1
fi

echo "== edl check: project-invariant static analysis =="
# the go-vet analog, specialized to THIS repo's contracts: donation
# safety, lockset races, recompile hazards, silent failures, telemetry
# conventions (edl_tpu/analysis/). Fails on any NON-BASELINED finding;
# deliberate violations carry `# edl: no-lint[rule]` comments at the
# site or a reasoned entry in analysis_baseline.json.
python -m edl_tpu.cli check --baseline analysis_baseline.json

echo "style OK"
