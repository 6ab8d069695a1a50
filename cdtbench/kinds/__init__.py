"""One file per model kind (the ``kind`` of a configuration's file), found
by that name: what the builder's tools need to build a model call
(``flops.py``) or a cell's denoise program (``offchip.py``) from the
program's own modules. Nothing here runs in a measured run. A new kind is a
new file with ``step_call`` and ``denoise_program``."""
