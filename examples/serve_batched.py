"""End-to-end serving driver: batched requests through prefill + KV-cache
decode for any architecture in the zoo (reduced config on CPU).

    PYTHONPATH=src python examples/serve_batched.py --arch gemma3-1b \
        --batch 8 --prompt-len 48 --gen 32

This is the same `prefill_step`/`decode_step` pair the multi-pod dry-run
lowers for the inference input shapes — here executed for real on CPU with
a reduced model, demonstrating rolling-window caches (gemma3/llama4),
SSM state caches (hymba/xlstm) and MLA latent caches (deepseek-v3).
"""
from repro.launch.serve import main
from repro.utils.cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    main()
