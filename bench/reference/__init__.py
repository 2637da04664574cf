"""Plain references, one module per plan family.  They import nothing of the
program and take nothing that it made."""
