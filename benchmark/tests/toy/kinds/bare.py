"""A kind that brings nothing: its files are written, hashed and left
alone as plain files are, nothing is warmed and nothing compared for
them, and a traffic mix never rewrites or deletes one."""
