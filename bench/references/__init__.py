"""Plain references, one module per feature script, named by a config's
``reference`` key.  They import nothing of the program under test."""
