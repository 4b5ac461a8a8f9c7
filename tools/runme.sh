#!/usr/bin/env bash
# Developer entry point (the reference's `./runme` analogue, L8 tooling).
#
#   tools/runme.sh test      full suite on the 8-virtual-device CPU mesh
#   tools/runme.sh quick     fast subset (core + gbdt + ops)
#   tools/runme.sh dryrun    multi-chip sharding dryrun (8 forced CPU devices)
#   tools/runme.sh smoke     chip_smoke.py (needs a TPU; exits 2 without one)
#   tools/runme.sh docs      regenerate docs/api.md from the stage registry
#   tools/runme.sh ci        everything the CI gate runs (tools/ci.sh)
set -euo pipefail
cd "$(dirname "$0")/.."

case "${1:-help}" in
  test)      python -m pytest tests/ -q ;;
  quick)     python -m pytest tests/test_core.py tests/test_gbdt.py tests/test_ops.py -q ;;
  dryrun)    JAX_PLATFORMS=cpu python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')" ;;
  smoke)     python chip_smoke.py ;;
  docs)      python tools/gen_api_docs.py ;;
  ci)        bash tools/ci.sh ;;
  *)         grep '^#   ' "$0" | sed 's/^#   //' ;;
esac
