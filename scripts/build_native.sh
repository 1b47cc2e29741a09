#!/bin/sh
# Build the native host-kernel library (native/ -> native/build/libblaze_native.so)
# with the one recipe the engine itself uses (blaze_tpu/utils/native.py).
set -e
cd "$(dirname "$0")/.."
python3 -c "from blaze_tpu.utils import native; print('native:', native.ensure_built(), native._SO_PATH)"
