"""Plain references, one architecture to a file, kept with the
benchmark so no later PR can change what a configuration is held to."""
