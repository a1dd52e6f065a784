"""Model modules: one per architecture, found by the ``"model"`` key of a
configuration file (``configs/<config>.json``), as ``models/<model>.py``.

The harness reaches model code only through these names:

``dims(conf)``
    the sizes the file states, as one hashable value with ``vocab`` and
    ``layers`` at least;
``program_config(conf)``
    the serving program's ``ModelConfig``;
``make(dims, seed, dtype)``
    seeded ``(params, buffers)`` in the program's tree layout, made on the
    device in one jitted call;
``served_gaps(dims, params, buffers, checked, max_len, control=False)``
    the plain reference (float32 at ``HIGHEST``, imported from nothing of
    the program) over each checked ``(prompt, served tokens)``: how far each
    served token's logit lies below the row's best; with ``control`` the
    float8-operand forward's first choice is judged instead;
``decode_attn_work(dims, lengths)``
    ``(flops, bytes)`` of one decode step's attention over lanes of context
    ``lengths``, summed over the stack's layers (a layer may count a window
    or another head shape);
``decode_token_flops(dims, n)``, ``prefill_flops(dims, start, n)``,
``head_flops(dims)``
    model FLOPs of one decode token at context ``n`` (head included), of
    prompt positions ``start .. start+n-1`` (no head), and of one head row;
``TINY``
    a configuration at a tiny size, which the CPU tests run through the
    whole harness.

``servbench/reference.py`` and ``servbench/weights.py`` hold what every
module shares.
"""
