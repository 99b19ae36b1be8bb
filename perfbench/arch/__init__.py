"""One module per ``architecture`` named in a configuration file; the
harness finds it by that name (``program.adapter``), as it finds the plain
reference by ``reference``.  A module gives:

- ``arch_config(cfg)``: the program's ``ArchConfig`` for the file (the
  only place an adapter imports the program);
- ``make_params(cfg, seed)``: the program's parameter tree, from the seed,
  on the device, in the type it is served in (draws from ``weights.py``);
- ``decode_work(cfg, positions, slots)`` and ``chunk_work(cfg, c, p0)``:
  the work of one decode step over lanes at ``positions`` in a program of
  ``slots`` lanes, and of one prefill chunk of ``c`` tokens from position
  ``p0``, as ``{"flops": operations the call needs, "kernels": {kernel
  event name: [(operations, bytes) of each of its calls]}}``.
"""
