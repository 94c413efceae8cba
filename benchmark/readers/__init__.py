"""One module per KIND of reader; a metric is a declaration under
``metrics/`` that names one of these and its parameters.  ``read(ctx,
**params)`` returns the number, or None when there is nothing to read (the
harness then leaves the metric out of the line)."""
