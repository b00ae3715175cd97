"""Runners: ``run(ctx) -> result`` drives one kind of cell through the
program's own entry point. A traffic mix names its runner."""
