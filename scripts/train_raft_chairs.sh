#!/bin/bash
# TPU re-make of the first command of upstream RAFT's launch script
# (reference: princeton-vl/RAFT train_standard.sh:3; the stage is
# abdo-eldesokey/RAFT-NCUP train.py's default): the first and longest stage
# of the curriculum, from scratch, BatchNorm trained (train.py:185-186
# freezes it for every stage BUT chairs). `--gpus 0 1` is dropped: one chip
# holds the batch of 10 whole, and the batch statistics are taken over it.
# Point --root_chairs at your data.
set -e
EXP=raft-chairs

python -u train.py \
  --name "$EXP" \
  --model raft \
  --stage chairs \
  --validation chairs \
  --num_steps 100000 \
  --batch_size 10 \
  --lr 0.0004 \
  --image_size 368 496 \
  --wdecay 0.0001 \
  "$@"
