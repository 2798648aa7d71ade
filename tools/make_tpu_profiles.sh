#!/usr/bin/env bash
# Generate the committed TPU chip profiles (profiles/README.md recipe) in one
# serialized chip session: per-layer profiles for ViT-B and ViT-L, the
# scheduler YAML conversions, and a bench.py run. Run from the repo root on a
# machine with the real chip. A chip belongs to one process at a time, so the
# steps run one after another.
set -euo pipefail
cd "$(dirname "$0")/.."
mkdir -p profiles/tpu

run() { echo "=== $*" >&2; stdbuf -oL -eL "$@"; }

# Profile into temp files and move into place ONLY on success: the
# profiler MERGES into an existing file and (reference semantics)
# refuses to re-profile a layer already present, so refresh runs need a
# fresh output — but deleting the committed fixtures up front would
# strand the tree with tracked files gone if an early step fails.
rm -f profiles/tpu/.tmp_vitb.yml profiles/tpu/.tmp_vitl.yml
run python profiler.py -m google/vit-base-patch16-224 -b 8 -t bfloat16 \
    -o profiles/tpu/.tmp_vitb.yml
mv profiles/tpu/.tmp_vitb.yml profiles/tpu/profiler_results_vitb.yml
run python profiler.py -m google/vit-large-patch16-224 -b 8 -t bfloat16 \
    -o profiles/tpu/.tmp_vitl.yml
mv profiles/tpu/.tmp_vitl.yml profiles/tpu/profiler_results_vitl.yml

# -f: refresh runs overwrite the previous session's entries
run python profiler_results_to_models.py -f \
    -i profiles/tpu/profiler_results_vitb.yml -o profiles/tpu/models.yml
run python profiler_results_to_models.py -f \
    -i profiles/tpu/profiler_results_vitl.yml -o profiles/tpu/models.yml
# -dtm 16384: v5e HBM MB; -dtb 100000: ~100 Gbps per-link planning number
# for the scheduler's min(src,dst) bandwidth model.
run python profiler_results_to_device_types.py tpu-v5e -f \
    -i profiles/tpu/profiler_results_vitb.yml -o profiles/tpu/device_types.yml \
    -dtm 16384 -dtb 100000
run python profiler_results_to_device_types.py tpu-v5e -f \
    -i profiles/tpu/profiler_results_vitl.yml -o profiles/tpu/device_types.yml \
    -dtm 16384 -dtb 100000
python -c "import yaml; yaml.safe_dump(
    {'tpu-v5e': ['tpu0', 'tpu1', 'tpu2', 'tpu3']},
    open('profiles/tpu/devices.yml', 'w'))"

run python bench.py
run python bench_decode.py
run python tools/bench_train.py
