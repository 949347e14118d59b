"""The benchmark's plain reference: a frozen copy of the detector's jnp
paths (gather ROIAlign, sequential jnp NMS, no Pallas kernel, no folded
BN shortcut beyond what the config states), taken from ``mx_rcnn_tpu`` at
commit 62cc8b4 and cut loose from it.  Nothing here imports the program;
the benchmark runs it in float32 at ``highest`` matmul precision on
weights it initialises itself from the seed.  A later PR may not edit
these files, so a change to the program's model is measured against this
copy, never against itself."""
