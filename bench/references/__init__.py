"""Plain references, one module per block, found by name.

A configuration file (``configs/<config>.json``) may name its reference
with ``"reference": "<name>"``; the harness then loads
``references/<name>.py`` as it loads a metric, from the directory given
beside the benchmark first.  Without the key the reference is
``dense``.  A new block is a new module here, so a configuration that
needs one adds files and edits none.

A module defines:

* ``dims_of(model) -> dims``: the reference's shape from the
  configuration file; ``dims`` is handed back to every function below,
  and to the metrics as ``Run.dims``;
* ``layout(dims) -> {path: shape}``: every weight the served model
  holds, by its path in the program's tree, per layer for the stacked
  ``layers/...`` leaves; the served weights must have exactly this
  layout;
* ``check_registry(cfg, dims)``: raises ``ValueError`` naming each
  difference where the program's ``ModelConfig`` is not the block the
  module computes;
* ``forward(model, seed, tokens, alt=None, quant=False, device=None)``:
  the teacher-forced forward over right-padded rows ``tokens`` (B, S),
  returning per position the best logit, the logit of the next token,
  the logit of ``alt``'s token and the forward's own first choice
  (``dense.Reading``).  ``quant=True`` is the control: the same forward
  in the precision below the one the configuration states;
* the work counts of its steps and kernels that the metrics it is used
  with read (``dense`` has those of ``mfu.prefill``, ``mfu.decode``,
  ``flash_prefill_roofline`` and ``paged_attention_roofline``): counts
  of the algorithm, not of an implementation;

and may define ``weight_law(path, shape, tied) -> (mean, std) | None``
for leaves whose law ``weights.py`` does not know (a router, an expert
bank).  The harness asks it first for every served leaf, and the
module's forward hands it to ``weights.layer_params`` and
``weights.leaf`` as ``extra``; None leaves a leaf to ``weights.py``.

The forward is plain ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``, written from the published description of the
model; it imports nothing of the program and takes none of its arrays,
and draws its weights from ``weights.py`` with the run's seed.  Each
place where the block departs from the published description is listed
in the configuration file (``reduced``, ``departures``).
"""
