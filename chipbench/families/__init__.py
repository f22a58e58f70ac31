"""Model families: what the harness must know of a model's architecture.

A configuration file names its family with ``"family": "<name>"`` (no
key: ``dense``).  ``harness.Bench.family`` loads ``families/<name>.py``
by path, so a new family is a new file here and nothing else changes.
A family module gives:

``dims(conf) -> dict``
    The sizes the configuration file states, as the family's other
    functions take them; ``vocab`` among them (the check reads it).
``check(dims, cfg) -> None``
    Raises where the program's ``ModelConfig`` is not the one the file
    states.
``deployed_shapes(cfg)``
    The abstract tree the program serves: ``jax.eval_shape`` of its
    ``init_lm`` with every quantized linear, expert banks included,
    replaced as ``repro.quant.export`` replaces it by a
    ``DeployedQuantState``.  Scan units sit under ``units`` with their
    leading unit axis.
``mean_abs_in(names) -> float``
    Mean |x| of the float input of the linear (``DeployedQuantState``)
    whose tree path has these key names: ``chipbench.weights`` sets its
    exponents from it.
``float_init(names, shape) -> (mean, std)``
    How the float leaf at a path is drawn, from the path's key names and
    the leaf's shape (a unit's own, below ``units``): the constant
    ``mean`` where ``std`` is 0, else uniform values of that std about
    it.
``reference(key, cfg, dims, abstract, t_pad)``
    The plain model (``chipbench.reference``'s shared pieces, no code of
    the program), with its weights drawn from the seed's leaf keys
    ``key`` through ``chipbench.weights`` over ``abstract``; sequences
    are padded to ``t_pad``.  Its ``logits(seqs, rows, bits=None)``
    gives float32 logits at the given positions of each sequence, at
    ``bits``-bit integer widths where given (the control).
``call_costs(dims, call, peak) -> dict``
    Least device seconds of the APSQ GEMMs (``gemm_s``) and of the KV
    attention (``attn_s``), model operations (``model_ops``) and tokens
    of one engine dispatch record (``harness.Dispatches``: its kind, its
    rows or chunk, and each emitted token's context), from
    ``chipbench.counts``' per-kernel counts and the chip's peaks.  The
    record holds what the engine's host side knows; work that depends on
    the values on the device (which experts a token routes to) is not in
    it, so a family counts what its kernels run for those shapes (the
    program's capacity-bound expert grid runs every expert at full
    capacity).
"""
