"""Megabytes of residuals the model's blocks keep through their
rematerialisation in one step, off the window's ``fetch`` spans that
carry ``attrs.kept_residual_mb``. A constant of the step program, so
every span says the same and the last one is read; 0 when every block's
forward is run again on the way back. A program that does not count it
gives nothing to read."""

from . import span_attrs


def read(context):
    kept = span_attrs.values(context, "fetch", "kept_residual_mb")
    return float(kept[-1]) if kept else None
