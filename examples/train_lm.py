"""End-to-end LM training driver (the ~100M-parameter preset).

    PYTHONPATH=src python examples/train_lm.py --arch phi4-mini-3.8b \
        --preset 100m --steps 300 --batch 4 --seq 256

Delegates to repro.launch.train — the same train_step the 512-chip dry-run
lowers, executed for real on CPU at a reduced scale.  Use --preset smoke
for a fast sanity run; checkpointing via --checkpoint ckpt/run1.
"""
import sys

from repro.launch.train import main
from repro.utils.cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    main(sys.argv[1:] or ["--arch", "phi4-mini-3.8b", "--preset", "100m",
                          "--steps", "300", "--batch", "4", "--seq", "256"])
