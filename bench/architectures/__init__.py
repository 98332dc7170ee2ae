"""One file per ``model_type``: everything of the benchmark that depends on
the architecture. ``bench.manifest.architecture`` finds
``bench/architectures/<model_type>.py`` by the configuration's own
``model_type`` key; no other file under ``bench/`` names an architecture, a
tensor, a special token or a template.

A later PR brings a new architecture as files and entries only: this file,
a configuration (``bench/configs``), a mix, a cell, readers. The file gives
five things (``mistral.py`` is the pattern; the parent process loads it too,
so it imports JAX inside its functions only):

1. the checkpoint's tensor table, ``top_tensors(cfg)`` and
   ``layer_tensors(cfg, i)``: HF tensor name -> ``(shape, draw)`` in the
   order of the file, ``draw`` one of ``bench.checkpoint.DRAWS`` (``normal``
   at ``initializer_range``, ``ones``, ``zeros``, ``head``: normal with the
   rows of the special ids zero). A function of the layer, so a leading
   dense layer or a layer pattern can differ; per-expert names, biases, q/k
   norms are entries like any other, and a tied head is no ``lm_head``
   entry (the embedding is then drawn as ``head``);
2. the plain reference, ``forward_logits(reader, cfg, sequences,
   first_rows, timing)``: float32 at matmul precision ``highest``, one layer
   of weights on the device at a time, nothing of ``cake_tpu``;
3. the template as the program renders that ``model_type``
   (``cake_tpu/models/llama/chat.py``): ``special_words(cfg)`` (id -> word,
   from the configuration's ``bos_token_id`` / ``eos_token_id``; traffic
   never draws these ids and ``head`` zeroes their rows), ``UNKNOWN_WORD``,
   ``chat_text(user)`` and ``chat_ids(cfg, prompt_ids)``;
4. its cost functions: ``decode_weight_bytes(cfg, dtype)``, and beside it
   the operations and bytes of each kernel the architecture brings, for a
   ``python`` reader to divide by the kernel's device time
   (``op_mean_us`` in ``bench/readers.py``);
5. nothing else: what only the benchmark needs to know about a deployment
   (a rank's share of experts, say) goes inside the configuration's
   free-form ``deployment`` object.
"""
