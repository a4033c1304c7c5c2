"""Plain float32 references, written from the published descriptions.
They import nothing of the program and take nothing it has made."""
