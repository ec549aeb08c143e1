"""One module per kind of run: ``train`` and ``serve``. Each has
``run(cell) -> common.RunResult``."""
