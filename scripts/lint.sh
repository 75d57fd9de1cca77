#!/usr/bin/env bash
# Static preflight lint over the harness + examples — the Python-side
# companion of scripts/native_check.sh (g++ -Wall gate over native/) and
# scripts/sanitize.sh (TSAN/ASAN builds; SURVEY §5: the reference leans on
# Go's race detector, our harness leans on determined_tpu/lint).
#
# All targets are passed in ONE invocation on purpose: the whole-program
# concurrency pass (lock-order-cycle / blocking-under-lock /
# signal-handler-unsafe) builds a single cross-module lock-acquisition
# graph spanning the package, scripts and examples — a script that
# takes package locks in the wrong order closes a cycle only a joint
# graph can see.  The serving tier (determined_tpu/serve: allocator
# free-list, admission queue, lane table, replica heartbeat thread) lints
# as part of the package target; its runtime counterpart is the
# lock_order + no_thread_leaks marker set tests/test_serving.py runs under.
#
# Strict mode: ANY finding fails.  Findings that are safe by a subtler
# argument carry inline `# dtpu: lint-ok[rule]` suppressions WITH the
# argument as a comment — new findings mean new code needs the same
# treatment (fix it, or argue it inline), so CI exits non-zero.
#
#   scripts/lint.sh            # lint the package + examples
#   scripts/lint.sh --json     # machine-readable (same gate)
set -euo pipefail
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"
# --exclude: a checkout that has hosted live experiments accumulates
# checkpoint dirs, experiment journals, exported traces, and shipped
# context code under the tree; none of that is this program (and context
# dirs carry user .py files).  The globs prune those directories before
# the walk instead of parsing whatever they contain.
#
# --native: the control-plane contract pass (docs/lint.md) — WAL
# replay/snapshot/fuzz completeness, route/API.md/metrics drift,
# fake-master conformance, dead agent wire fields.  Same strict gate:
# drift between master.cpp and the Python side fails CI here.
exec python -m determined_tpu.cli lint --strict --native \
  --exclude 'checkpoints' --exclude 'checkpoints/*' \
  --exclude 'traces' --exclude 'traces/*' \
  --exclude '*.egg-info' --exclude 'build' \
  --exclude 'dtpu-ctx-*' \
  "$@" determined_tpu examples scripts
