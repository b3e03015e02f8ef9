#!/bin/bash
# On-chip sweep of every CLI registry pipeline at its default demo config
# (the round-4/5 acceptance pattern: TPU-only latent failures — scoped-VMEM
# overflows, layout traps — are swept on hardware, not just asserted on the
# CPU mesh). One process per pipeline (one process owns the chip at a time);
# a failure does not stop the sweep, but the sweep exits non-zero if any
# pipeline failed. Each run's last line names the device it ran on.
set -u
cd "$(dirname "$0")/.."
out="${1:-/tmp/pipeline_sweep.log}"
: > "$out"
names="MnistRandomFFT TimitPipeline LinearPixels RandomCifar RandomPatchCifar RandomPatchCifarKernel RandomPatchCifarAugmented VOCSIFTFisher ImageNetSiftLcsFV AmazonReviewsPipeline NewsgroupsPipeline StupidBackoffPipeline"
ok=0; fail=0
for name in $names; do
  echo "=== $name ===" >> "$out"
  if timeout 540 python -m keystone_tpu.run "$name" >> "$out" 2>&1; then
    echo "OK $name"; ok=$((ok+1))
  else
    echo "FAIL $name"; fail=$((fail+1))
  fi
done
# The auto-solver TIMIT path is the round-5 addition: sweep it explicitly.
echo "=== TimitPipeline --solver auto (explicit) ===" >> "$out"
if timeout 540 python -m keystone_tpu.run TimitPipeline --solver auto >> "$out" 2>&1; then
  echo "OK TimitPipeline--solver-auto"; ok=$((ok+1))
else
  echo "FAIL TimitPipeline--solver-auto"; fail=$((fail+1))
fi
echo "SWEEP DONE ok=$ok fail=$fail (log: $out)"
[ "$fail" -eq 0 ]
