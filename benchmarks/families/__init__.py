"""Each configuration names its family (``"family"`` in its file): one
module here that is everything the harness knows of an architecture
(``harness/schema.load_module`` finds it by name).  The drivers and the
readers reach it through ``run.family`` and read no size of a
configuration themselves (only ``vocab_size``, which every published
configuration calls that).

What the harness reads of a family, and so what one must provide:

``build_model(cfg, **kwargs)``      the program's model, through the
    constructor the trainer and ``tools/serve_lm.py`` use.  The train
    driver passes ``dropout_rate``, ``dtype`` and ``remat``, the serve
    driver ``dtype``.
``init_fn(cfg)``                    ``seed -> parameter tree``, not
    jitted, the seed an argument; ``init_params(cfg, seed,
    sharding=None)`` the tree on the device by one jitted call, in the
    type the configuration states it stores and serves them in.
``train_flops_per_token(cfg, seq_len)``, ``decode_step_flops(cfg,
live_rows, slots)``, ``decode_step_bytes(cfg, live_rows)``   the work a
    step REQUIRES, from shapes (``harness/peaks.roofline_seconds`` turns
    it into a time; the bytes count the weights in the type they are
    stored in).

Whatever else a family keeps (parameter counts, a forward count) is its
own.  The configuration's plain reference (``"reference"``, under
``benchmarks/reference/``) is handed the configuration and reads its
own keys."""
