"""What one shape of statement means, one module a ``semantics.kind``
(reference.py has the interface).  Plain numpy; a module here imports
nothing of the program and nothing of benchmark/ but ``reference``."""
