"""Architecture modules of the chip benchmark, one file a Hugging Face
``model_type``: ``arch/<model_type>.py``, chosen by the configuration
file's own ``model_type`` key (``registry.arch``).  A type with no file
here is refused, never run as another architecture.

Each module gives:

* ``model_config_kwargs(config)`` -- the program's
  ``repro.configs.base.ModelConfig`` keyword arguments for the
  configuration file;
* ``numbers(cfg)`` -- the sizes a run records (``record["model"]``),
  from that ``ModelConfig``; the functions below read them;
* ``weights(m, seed)`` and ``served_gaps(w, m, prompt, served,
  control=)`` -- the plain reference, which imports nothing of the
  program, and the comparison that decides ``correct``;
* ``decode_flops(m, lens)`` and ``prefill_flops(m, cached, total)`` --
  the model's operations in one decode step over lanes of valid lengths
  ``lens``, and in prefilling positions ``cached .. total - 1``;
* ``KERNELS`` -- ``{kernel name: work(m, lens) -> (flops, bytes)}`` of
  one decode step, by the name ``trace_reduce`` tells the kernel by.
"""
