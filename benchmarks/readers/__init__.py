"""One reader kind to a file: ``read(args, ctx) -> float | None``."""
