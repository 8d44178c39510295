#!/bin/bash
# Round-5 data-augmentation sweep (VERDICT r4 #3): can augmentation
# push held-out eval/identity_pred past the 0.828 distillation ceiling
# (teacher peak 0.808 @ step 666; CCS baseline 0.922)?
#
# Protocol matches artifacts/heldout_r4 exactly (same data, seed,
# schedule: transformer_learn_values+test, b32, warmup 100) except for
# the augmentation flags; best checkpoint tracked by held-out
# eval/identity_pred at a finer eval cadence (114 = 3 evals/epoch-ish).
#
#   bash scripts/augment_sweep.sh [sweep_names...]   (default: a b c)
set -u
REPO=/root/repo
DATA=${DC_AUG_DATA:-/root/data_r4/examples}
EPOCHS=${DC_AUG_EPOCHS:-60}
OUTROOT=${DC_AUG_OUT:-/root}
export PYTHONPATH=$REPO

train_one() {  # name extra --set flags...
  local name=$1; shift
  local out="$OUTROOT/aug_r5_$name"
  echo "=== sweep $name -> $out ==="
  python - train --config transformer_learn_values+test \
    --out_dir "$out" \
    --train_path "$DATA/train/*" --eval_path "$DATA/eval/*" \
    --batch_size 32 --num_epochs "$EPOCHS" \
    --set eval_every_n_steps=114 --set warmup_steps=100 \
    --set num_epochs_for_decay="$EPOCHS" \
    --set best_checkpoint_metric=eval/identity_pred \
    --set augment=true "$@" <<'EOF'
import jax, sys
jax.config.update('jax_platforms', 'cpu')
from deepconsensus_tpu.cli import main
sys.exit(main(sys.argv[1:]))
EOF
  rc=$?
  if [ $rc -ne 0 ]; then
    echo "sweep $name FAILED rc=$rc"
    return $rc
  fi
  echo "--- $name trajectory (eval/identity_pred) ---"
  cut -f1,8 "$out/checkpoint_metrics.tsv" 2>/dev/null | tail -25
  cat "$out/best_checkpoint.txt" 2>/dev/null
}

[ $# -eq 0 ] && set -- a b c
for sweep in "$@"; do
  case $sweep in
    a)  # orientation + order only: the two exactly-label-preserving
        # transforms at default strength.
      train_one a --set augment_drop_prob=0.0 --set augment_jitter_prob=0.0
      ;;
    b)  # all four transforms at default strength.
      train_one b
      ;;
    c)  # aggressive: always reorder, heavier downsample/jitter.
      train_one c --set augment_perm_prob=1.0 --set augment_drop_prob=0.5 \
        --set augment_jitter_prob=0.5
      ;;
    *) echo "unknown sweep $sweep"; exit 2;;
  esac
done
