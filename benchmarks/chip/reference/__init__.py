"""Plain references, one module an architecture, chosen by name.

A configuration file names its reference (``"reference": "dense_gqa"``), and
``harness.reference(c)`` imports ``reference/<name>.py``. So an architecture
joins the benchmark by new files alone: its configuration and its reference
module. A reference imports nothing of the program under test and takes
nothing the program has made. What a module keeps, by what calls it
(``harness.REFERENCE_NEEDS`` and each kind's ``REFERENCE`` list the names;
``harness.kind_for`` refuses a run whose reference lacks any of them, before
set-up):

Every run:

* ``make_params(c, seed, dtype) -> params``: every weight from the 31-bit
  ``seed``, on the device, in one jitted call, in the program's parameter
  tree and ``dtype``. Called in set-up for the program, and again in the
  check: the same jitted function rounds alike on both sides.
* ``arch_fields(c) -> dict``: the fields of the program's ``ArchConfig``
  that the file sets, by the program's names. ``harness.program_config``
  applies them to the registry entry of ``c["program"]["arch"]``. A
  reference maps its own keys: a DeepSeek-V3 file's ``q_lora_rank`` null to
  0, ``n_routed_experts`` to ``n_experts``, ``num_experts_per_tok`` to
  ``top_k``, ``first_k_dense_replace`` to ``first_dense_layers``,
  ``scoring_func`` to ``router_type``.

The train kind (``kinds/train.py``):

* ``flat(tree) -> {"a/b": leaf}``: a parameter tree by its paths, in the
  program's tree order.
* ``train_steps(c, opt, params0, batches, fmt=None) -> dict``: AdamW steps
  from ``params0`` over ``batches`` (``(tokens, labels)`` each), mixed
  precision as the file states it; ``losses`` a step, the first step's
  clipped gradient ``first_grad`` and the float32 ``master`` after the last,
  each leaf by ``flat``'s path.
* ``train_step_flops(c, seq_len, batch)``: the operations of one training
  step from the file's shapes, whatever implements them (``train_step_mfu``
  reads it as ``run.flops``).

The serve kind (``kinds/serve.py``):

* ``served_gaps(params, prompt, served, c, fmt=None) -> dict``: for one
  request, ``served_gap``, the widest gap by which a served token's logit
  lies below the reference's best; with ``fmt``, also ``control_gap``, the
  same for the tokens ``fmt`` would have put first.
* ``decode_flops(c, pos)``, ``decode_bytes(c, pos)``: the operations and
  bytes of one B=1 decode step at cache position ``pos``
  (``decode_step_roofline``, ``decode_step_mfu``).

``fmt`` is the operand format of every matrix product: ``None`` is float32
at ``highest`` precision, ``"fp8"`` the control of the correctness check
(the nearest precision below the configuration's bfloat16).
"""
