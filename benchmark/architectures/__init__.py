"""One package per architecture: ``<name>/{keys,shapes,weights,reference}.py``,
found by the ``architecture`` key of a configuration file
(``harness/arch.py``; README.md says what each module has to offer)."""
