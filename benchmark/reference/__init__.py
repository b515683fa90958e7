"""Plain references: NumPy on the host, nothing imported from the program."""
