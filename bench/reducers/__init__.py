"""One module per reducer, named by a metric file's ``reducer``.  Each has
``reduce(ctx, **args) -> float | None``; None means it found nothing to
read, and the harness leaves the metric out."""
