"""Plain references the benchmark compares the trainer with.

A configuration names its reference (`"reference": "<module>"`), a module
of this package.  That module is the one place that knows the model's
block; the harness, the training loop (`train.py`), the control and the
counts take everything model-specific from it:

  sizes(model) -> dict          the sizes the file's `model` implies
  check_program(cfg, m)         raises ValueError where the trainer's
                                ModelConfig differs from `m`, width for width
  param_count(m) -> int         every parameter
  flops_per_token(m, seq_len)   forward and backward operations per trained
                                token, recomputation not counted
  seed_key(seed), init(key, m, dtype)
                                the weights from the seed, in the trainer's
                                order and under its leaf paths (the
                                comparison matches leaves by path)
  block_grad(m)                 (params, tokens, labels) -> (loss sum, valid
                                count, gradient of the loss sum) over one
                                block of rows, unjitted
  sqnorm(tree), leaf_norms(tree), host_leaves(tree)

Adding a configuration with another block adds a module here and edits
nothing else.
"""
