"""One module per architecture block, found by name.

A configuration file under ``bench/configs/`` names its block at its top
level (``"block": "dense_gqa"``), and ``Registry.block`` loads
``bench/blocks/<block>.py``.  Everything the benchmark knows of an
architecture lives in that module, so a new architecture enters as new
files.  A block module defines:

``spec(conf)``
    The block's frozen, hashable shape from the configuration file.  It
    raises ``ValueError``, with the reason, for a configuration the block
    cannot run.
``make_weights(spec, seed)``
    Every weight, made from ``seed`` (``reference.seed_key``) in one jitted
    call on the device, in the dtype it is served in.
``program_fields(spec, name)``
    A dict of the program's ``ArchConfig`` fields for this shape.  ``groups``
    is given as ``(pattern, repeats)`` pairs; ``serving_adapter`` turns them
    into the program's scan groups and sets ``use_kernels`` while the field
    exists.
``program_params(weights, padded_vocab)``
    The program's parameter tree holding these weights, the vocabulary
    padded to ``padded_vocab`` rows.
``served_gaps(weights, spec, prompts, outputs, control=False)``
    The float32 reference, layer by layer under
    ``default_matmul_precision("highest")``, teacher-forced over each
    prompt and its served tokens (``reference.served_gaps``): the gap of
    each served token and, with ``control``, of the float8 control's.
``vocab(spec)``, ``kv_bytes_per_token(spec)``
    The vocabulary the traffic draws from, and the KV cache bytes one token
    holds over all layers.
``decode_flops(spec, ctx_len, counters)``
    The FLOPs of the whole step that one token decoded at context
    ``ctx_len`` requires.
``decode_attn(spec, ctx_len, counters)``
    The (FLOPs, bytes) of that token's attention.

In both, ``counters`` is what the engine counted over the traced steps
only, the same steps whose tokens the readers count (a flat dict of its
counters and histogram counts, ``Server.counters()`` at the trace's stop
less at its start; empty where nothing was traced).  It is for work that
depends on what the program did, such as how many experts a batch hit.  A
block whose work does not depend on it ignores it.

Shared pieces (float8 rounding, RMSNorm, RoPE, the teacher-forced loop and
the gaps) are in ``bench/reference.py``; a block may import another
block's parts, as ``bench.blocks.dense_gqa``.
"""
